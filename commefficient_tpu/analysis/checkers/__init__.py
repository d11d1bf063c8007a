"""The flowlint rule registry — both tiers, declaratively.

* :data:`LEGACY_RULES` — the per-file rules migrated verbatim from
  the grep-era ``analysis/lint.py`` (same names, same findings, same
  waivers; pinned identical by tests/test_flowlint.py).
* :data:`FLOW_CHECKERS` — the whole-program checkers that need the
  call graph / symbol table: trace-purity, prng-keys,
  wire-dtype-crossing, lock-confinement.

``scripts/audit.py`` runs both tiers and gates them through the same
baseline; ``# audit: allow(<rule>)`` waivers work identically for
either tier.
"""

from commefficient_tpu.analysis.checkers.legacy import (  # noqa: F401
    COMPILED_SCOPE,
    HOST_HOT_PATH,
    LEGACY_RULES,
    LEGACY_RULES_BY_NAME,
)
from commefficient_tpu.analysis.checkers.locks import (
    CHECKER as LOCK_CONFINEMENT,
)
from commefficient_tpu.analysis.checkers.prng import (
    CHECKER as PRNG_KEYS,
)
from commefficient_tpu.analysis.checkers.purity import (
    CHECKER as TRACE_PURITY,
)
from commefficient_tpu.analysis.checkers.wire import (
    CHECKER as WIRE_DTYPE_CROSSING,
)

FLOW_CHECKERS = [
    TRACE_PURITY,
    PRNG_KEYS,
    WIRE_DTYPE_CROSSING,
    LOCK_CONFINEMENT,
]

FLOW_CHECKERS_BY_NAME = {c.name: c for c in FLOW_CHECKERS}
FLOW_RULE_NAMES = sorted(FLOW_CHECKERS_BY_NAME)
