"""Queries answered per second: every query row whose answer came back
inside the window, over the window's length (host clock)."""


def read(run):
    done = sum(op.rows for op in run.ops if op.kind == "query" and op.error is None
               and run.w0 <= op.done < run.w1)
    return done / run.seconds if done else None
