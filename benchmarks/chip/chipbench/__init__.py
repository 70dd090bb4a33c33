"""The on-chip cell benchmark of the AME memory service.

`run.py` beside this package runs one cell of `BENCHMARK.json` once.  The
package is the yardstick: it generates each cell's data and traffic from the
seed, drives `repro.api.MemoryService` through its public entry points,
reduces the profiler trace, and compares what the timed path returned with a
plain reference that imports nothing of the program.

Everything that belongs to one configuration, traffic mix or metric lives in
a file of its own beside this package, found by its name:

    configs/<config>.json     deployment sizes, engine settings, limits
    traffic/<traffic>.json    parameters of the general traffic generator
    metrics/<metric>.py       a reader: `read(run) -> float | None`
"""
