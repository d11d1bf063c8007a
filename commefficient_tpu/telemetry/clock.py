"""The ONE place raw wall/interval clocks live.

Every other module in ``commefficient_tpu`` times through these
aliases (or, better, through ``Telemetry.span``) so that a tier-1
grep test (tests/test_telemetry.py) can keep ad-hoc ``time.time()`` /
``perf_counter()`` timing from creeping back into the codebase — the
pre-telemetry state was three disjoint, schema-free views of the same
run (trainer state dicts, per-script JSON, a barely-used
``--tensorboard`` flag).

``wall``  — epoch seconds, for timestamps humans correlate with logs.
``tick``  — monotonic high-resolution clock, for intervals/spans.
``thread_cpu`` — CPU seconds the calling thread has used (user +
            system): beside ``tick`` on every span, so that a span's
            wall time splits into computing and waiting.
"""

from __future__ import annotations

import time

wall = time.time
tick = time.perf_counter
thread_cpu = time.thread_time
