"""Deterministic chaos engineering for the orchestration layer.

The fault-injection capability SURVEY.md 5.3 notes the reference never
had, grown into a first-class subsystem: a seeded, reproducible fault
schedule (plan.ChaosPlan — same seed, same injection sequence),
injectors threaded through the framework's existing seams
(injectors — store op delay/error wrappers, heartbeat blackout, task
SIGKILL mid-run, SIGSTOP wedge, node preemption on the fakepod
substrate), and a scenario runner (drill.run_drill) that drives a real
fakepod pool through the schedule and asserts the self-healing
invariants: every task completes, no orphaned gang rows or queue
messages, and the goodput partition stays exact.

Surfaces: `shipyard chaos plan|drill` (cli) and tools/chaos_drill.py
(standalone runner).
"""

from batch_shipyard_tpu.chaos.plan import (  # noqa: F401
    ChaosPlan, Injection, INJECTION_KINDS)
from batch_shipyard_tpu.chaos.injectors import (  # noqa: F401
    ChaosStore)
from batch_shipyard_tpu.chaos.drill import run_drill  # noqa: F401
