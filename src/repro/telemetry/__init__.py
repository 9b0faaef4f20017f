"""Host-clock telemetry for the serve launcher and the comm-time bench.

The train launcher's ``--trace`` uses the profiler instead
(``jax.profiler``: device ops with their named scopes and the loop's
host spans on one clock, ``docs/observability.md``). What remains here
is the host-clock side that ``launch/serve.py`` and
``benchmarks/bench_comm_time.py`` still use:

* :mod:`repro.telemetry.trace` — the event model. ``TraceEvent`` (one
  completed span, microsecond units), ``TraceRecorder`` (bounded ring
  buffer), JSONL event-log read/write and a lossless Chrome-trace
  (``chrome://tracing`` / Perfetto) export. Schema documented in
  ``docs/observability.md``.
* :mod:`repro.telemetry.timers` — ``StepTimer``: spans with
  ``jax.block_until_ready`` fencing at the boundaries when tracing is
  on, and a zero-cost no-op path when off (``timed_step`` returns the
  wrapped callable *unchanged* — same object — so the traced program
  cannot differ).
* :mod:`repro.telemetry.probes` — per-matching ppermute probes, each
  matching's exchange timed as its own fenced executable.

Nothing here imports ``repro.dist`` at module scope (probes import it
lazily), so importing telemetry never changes what a step traces.
"""
from __future__ import annotations

from repro.telemetry.timers import PHASES, StepTimer, timed_step
from repro.telemetry.trace import (
    TraceEvent,
    TraceRecorder,
    from_chrome_trace,
    read_jsonl,
    to_chrome_trace,
    write_chrome_trace,
    write_jsonl,
)

__all__ = [
    "PHASES",
    "StepTimer",
    "TraceEvent",
    "TraceRecorder",
    "from_chrome_trace",
    "read_jsonl",
    "timed_step",
    "to_chrome_trace",
    "write_chrome_trace",
    "write_jsonl",
]
