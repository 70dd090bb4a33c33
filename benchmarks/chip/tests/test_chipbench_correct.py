"""The comparison that decides `correct`: sound runs pass it, a dropped
acknowledged row fails it, and the controls (the reference rounded to fp8
or to int8, put in the program's place) fail it, on tiny deployments on
the CPU."""
import numpy as np
import pytest

import tiny
from chipbench import reference as ref


def test_dropped_acknowledged_row_is_caught():
    # ids 0..3 stored; 4 and 5 inserted and acknowledged; 1 deleted
    tl = ref.Timeline(ins_sub=np.array([-np.inf] * 4 + [1.0, 2.0]),
                      ins_ack=np.array([-np.inf] * 4 + [1.5, 2.5]),
                      del_sub=np.array([np.inf, 3.0] + [np.inf] * 4),
                      del_ack=np.array([np.inf, 3.5] + [np.inf] * 4))
    assert ref.final_state_checks(tl, np.array([0, 2, 3, 4, 5])) == {
        "lost_rows": 0, "resurrected_rows": 0}
    assert ref.final_state_checks(tl, np.array([0, 2, 3, 4]))["lost_rows"] == 1
    assert ref.final_state_checks(tl, np.array([0, 1, 2, 3, 4, 5]))["resurrected_rows"] == 1
    assert ref.final_state_checks(tl, np.array([0, 2, 3, 4, 5, 5, 9]))["resurrected_rows"] == 2


@pytest.mark.parametrize("config, mix", [(tiny.TOPICS, tiny.AGENT), (tiny.CLUSTERS, tiny.STREAM)],
                         ids=["topics", "clusters"])
def test_sound_run_is_correct_and_the_control_is_not(config, mix):
    out = tiny.run(tiny.cell(config, mix), seconds=2.0, controls=("fp8", "int8"))
    checks = {k: v["value"] for k, v in out["checks"].items()}
    assert out["correct"], checks
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    # each control, the reference in a precision below the configuration's
    # bf16 operands, goes through the same limits and comes out not correct
    limit = config["limits"]["score_err"]
    for precision in ("fp8", "int8"):
        control = out["controls"][precision]
        assert control["correct"] is False, control["checks"]
        assert control["checks"]["score_err"]["value"] > 3 * limit
        assert control["checks"]["wrong_ids"]["value"] == 0
    assert checks["score_err"] < limit / 10
