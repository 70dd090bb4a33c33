"""Host spans on the profiler's clock.

A span is a `jax.profiler.TraceAnnotation`: it records only while a
profiler session runs (about a microsecond when none does), and lands in
the same trace as the device's modules and ops, so a reader can put each
idle stretch of the device down to the request phase the host was in.
Every span name starts with `ame.`.

The scheduler's worker sets the request id of the task it runs
(`running`); every span opened on that thread until the task ends carries
it as `req`, so the phases of one request share an identifier.
"""
from __future__ import annotations

import contextlib
import contextvars

import jax

_REQ: contextvars.ContextVar = contextvars.ContextVar("ame_req", default=None)


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """A span named `name`, with `args` and the running request's `req`."""
    req = _REQ.get()
    if req is not None:
        args.setdefault("req", req)
    return jax.profiler.TraceAnnotation(name, **args)


def device(fn) -> jax.profiler.TraceAnnotation:
    """`ame.device`, from dispatching `fn` to its result on the host;
    `module` names fn's device program as the profiler does (`jit_<name>`
    for a jitted function)."""
    name = fn.__name__
    return span("ame.device", module=f"jit_{name}" if hasattr(fn, "lower") else name)


@contextlib.contextmanager
def running(req: int):
    """Mark this thread as running request `req` until the block ends."""
    token = _REQ.set(req)
    try:
        yield
    finally:
        _REQ.reset(token)
