"""One driver per kind of served model, found by the configuration's
``driver`` key (``chipbench/drivers/<driver>.py``, class ``Driver``).

A driver builds the system under test from a configuration and a traffic
file, runs one traffic cycle per ``cycle()`` call through the program's
``ServingEngine``, and after the window checks what the timed path
served against the plain reference.  What every driver shares is here.
"""
from __future__ import annotations

from repro.serving import ServingEngine


class BenchEngine(ServingEngine):
    """The program's engine, with the benchmark's host spans around the
    two calls it makes on the serving loop (a repartition, an admission);
    nothing else differs."""

    def __init__(self, mgr, *, spans, **kw):
        super().__init__(mgr, **kw)
        self._bench_spans = spans

    def execute_switch(self, strategy, new_split: int):
        with self._bench_spans("switch"):
            return super().execute_switch(strategy, new_split)

    def execute_admit(self, prompt, sid=None) -> str:
        with self._bench_spans("admit"):
            return super().execute_admit(prompt, sid=sid)


def schedule_link(engine, steps, strategy, active_split: int,
                  latency_ms: float) -> int:
    """Script one cycle's link steps: set the bandwidth at each step and
    repartition with ``strategy`` where the step's split differs from
    the one serving.  Returns the split serving at the cycle's end."""
    for at, mbps, split in steps:
        engine.schedule_network(at, mbps, latency_ms)
        if split != active_split:
            engine.schedule_switch(at, strategy, split)
            active_split = split
    return active_split


def program_config(name: str, values: dict):
    """The program's own registry entry ``name`` with the numbers of the
    benchmark's configuration file put in (``attr -> value``), so that
    the program runs exactly what the file states."""
    import dataclasses

    from repro.configs import get_config
    pcfg = dataclasses.replace(get_config(name), **values)
    bad = {k: (getattr(pcfg, k), v) for k, v in values.items()
           if getattr(pcfg, k) != v}
    if bad:
        raise ValueError(f"program config {name!r} does not hold the "
                         f"file's numbers: {bad} (program, file)")
    return pcfg
