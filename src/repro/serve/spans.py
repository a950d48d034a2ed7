"""Host spans of the serving engine, on the profiler's clock.

``span`` marks one host phase as a ``jax.profiler.TraceAnnotation``
named ``<layer>:<phase>`` — ``engine:`` for the phases of
``ContinuousBatcher.step``, ``frontend:`` for ``AsyncEngine``'s step
loop — so a profiler trace shows what the host was doing beside the
device's operations.  Given a ``phases`` dict (a step's
``StepStats.phases``) it also adds the phase's host-clock seconds there,
under the phase name.

The spans are always on: with no profiler running a span costs about
two microseconds, against engine steps of milliseconds.
"""
from __future__ import annotations

import time
from typing import Dict, Optional

import jax


class span:
    """``with span("engine:admit", phases):`` annotates the block with
    the trace arguments ``args`` and adds its seconds to
    ``phases["admit"]`` when ``phases`` is given.  Entering yields the
    annotation, whose ``set_metadata`` adds arguments known only at the
    end of the block."""

    __slots__ = ("name", "phases", "args", "ann", "t0")

    def __init__(self, name: str, phases: Optional[Dict[str, float]] = None,
                 **args):
        self.name, self.phases, self.args = name, phases, args

    def __enter__(self) -> jax.profiler.TraceAnnotation:
        self.t0 = time.perf_counter()
        self.ann = jax.profiler.TraceAnnotation(self.name, **self.args)
        return self.ann.__enter__()

    def __exit__(self, *exc) -> None:
        self.ann.__exit__(*exc)
        if self.phases is not None:
            phase = self.name.partition(":")[2]
            self.phases[phase] = (self.phases.get(phase, 0.0)
                                  + time.perf_counter() - self.t0)
