"""Table-driven classification of gcloud / Cloud TPU API errors.

Reference analog: the resize-error classification of
`/root/reference/convoy/batch.py:625-672` — Azure Batch surfaces a
typed `resize_errors` list; gcloud surfaces stderr text and JSON error
bodies, so the table below maps the payload shapes observed from real
`gcloud compute tpus tpu-vm create` / queued-resource failures onto a
stable classification the pool manager can act on:

  kind    — quota | stockout | permission | invalid_argument |
            conflict | not_found | unavailable | internal | unknown
  fatal   — retrying the SAME request cannot succeed (config/auth
            error) — the reference's "fatal resize error" bucket
  retry   — suggested recovery: none | backoff | other_zone

Rules are ordered; first match wins. Matching is case-insensitive
substring over the combined stderr/JSON text — gcloud is not
consistent enough across versions for anything stricter, which is
exactly why the table (not scattered `in` checks) is the API and why
the test corpus pins real captured payloads
(tests/test_gcloud_errors.py).
"""

from __future__ import annotations

import dataclasses
from typing import Optional


@dataclasses.dataclass(frozen=True)
class ErrorClass:
    kind: str
    fatal: bool
    retry: str           # none | backoff | other_zone
    rule: str            # the marker that matched (for logs)


# (marker, kind, fatal, retry) — ordered, first match wins.
_RULES: tuple[tuple[str, str, bool, str], ...] = (
    # Quota: permanent until the operator raises it.
    ("quota exceeded", "quota", True, "none"),
    ("quota_exceeded", "quota", True, "none"),
    ("exceeded quota", "quota", True, "none"),
    # Stockout/capacity: zone is dry; try elsewhere or wait. The
    # specific capacity phrasings come BEFORE the bare
    # RESOURCE_EXHAUSTED rule: GCP also returns RESOURCE_EXHAUSTED for
    # API rate limiting (HTTP 429), where other_zone would wrongly
    # abort the allocation — a bare status with no capacity wording
    # therefore backs off instead (advisor r2 finding #1).
    ("no more capacity in the zone", "stockout", False, "other_zone"),
    ("does not have enough resources available",
     "stockout", False, "other_zone"),
    ("stockout", "stockout", False, "other_zone"),
    ("not enough available capacity", "stockout", False, "other_zone"),
    ("insufficient capacity", "stockout", False, "other_zone"),
    ("resource_exhausted", "unavailable", False, "backoff"),
    # Config errors BEFORE the generic not-found rules: "Accelerator
    # type v5p-8 was not found" is a fatal config error, and the
    # generic "was not found" rule would otherwise classify it as a
    # non-fatal not_found and poll to timeout (advisor r2 finding #2).
    ("accelerator type .* not found", "invalid_argument", True,
     "none"),
    ("is not a valid accelerator-type", "invalid_argument", True,
     "none"),
    ("invalid value for field", "invalid_argument", True, "none"),
    ("unsupported runtime version", "invalid_argument", True, "none"),
    # Conflict / not-found / transient BEFORE the permission rules:
    # GCP conflates wording ("does not have permission ... or it may
    # not exist"), and a merely-mentioned "permission" must not brick
    # a pool when a more specific transient marker is present.
    ("already exists", "conflict", False, "none"),
    ("alreadyexists", "conflict", False, "none"),
    ("not_found", "not_found", False, "none"),
    ("was not found", "not_found", False, "none"),
    ("unavailable", "unavailable", False, "backoff"),
    ("service is currently unavailable", "unavailable", False,
     "backoff"),
    ("deadline_exceeded", "unavailable", False, "backoff"),
    ("deadline exceeded", "unavailable", False, "backoff"),
    ("connection reset", "unavailable", False, "backoff"),
    ("internal error", "internal", False, "backoff"),
    ("internal_error", "internal", False, "backoff"),
    ("rate limit", "unavailable", False, "backoff"),
    # Auth/permission: fatal, operator action required. Specific
    # phrasings only — a bare "permission" substring is too greedy.
    ("permission denied", "permission", True, "none"),
    ("permission_denied", "permission", True, "none"),
    ("permission '", "permission", True, "none"),
    ("does not have permission", "permission", True, "none"),
    ("request had insufficient authentication",
     "permission", True, "none"),
    ("unauthenticated", "permission", True, "none"),
    # Config errors: fatal, same request can never work. (The
    # specific phrasings live above the not-found rules; the bare
    # status string stays down here below the permission rules.)
    ("invalid_argument", "invalid_argument", True, "none"),
)


def classify(payload: str) -> ErrorClass:
    """Classify a gcloud failure payload (stderr text, JSON error
    body, or both concatenated)."""
    import re
    text = payload.lower()
    for marker, kind, fatal, retry in _RULES:
        if ".*" in marker:
            if re.search(marker, text):
                return ErrorClass(kind, fatal, retry, marker)
        elif marker in text:
            return ErrorClass(kind, fatal, retry, marker)
    return ErrorClass("unknown", False, "backoff", "")


def is_preemption_state(state: Optional[str]) -> bool:
    """Cloud TPU node states that mean the slice was taken away
    (spot/preemptible reclamation or maintenance) rather than deleted
    by us — the signal feeding slice-recreate recovery."""
    return (state or "").upper() in ("PREEMPTED", "TERMINATED",
                                     "SUSPENDED")
