"""The program's set-up stage seconds, heard as JAX monitoring events.

The program reports the host seconds of each set-up stage as the duration
event ``/repro/setup/<stage>``: ``preprocess_graph`` its ``partition``,
``expand``, ``pad`` and ``budgets``, ``KGETrainer`` its parameter and
optimizer ``init``.  ``bench/run.py`` loads every per-layer reader before
the cell's trainer is built, so the listener this module registers when
it is first imported hears the whole set-up; ``seconds`` keeps the latest
reading of each stage.  A program that reports no stages leaves it empty,
and the readers then report nothing.
"""
from __future__ import annotations

from typing import Dict

import jax.monitoring

PREFIX = "/repro/setup/"
seconds: Dict[str, float] = {}


def _listen(event: str, secs: float, **_) -> None:
    if event.startswith(PREFIX):
        seconds[event[len(PREFIX):]] = secs


jax.monitoring.register_event_duration_secs_listener(_listen)
