"""Cell enumeration for the conformance matrix.

A cell is ``family×mode×backend``. The fast subset — every family ×
every failure mode on the localfs package (swap cells cross packages by
definition) — runs in tier-1; the sharded backend axis and the second
MoE family (top-k>1 routing) ride behind the ``slow`` marker.

``expected_cells.json`` pins the fast subset's IDs; ``check_report.py``
fails CI when a previously-green cell goes missing or skipped, and
``test_matrix.test_expected_cells_manifest_in_sync`` keeps the pin from
drifting out from under a family addition.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List

FAMILIES = ("attention", "moe", "ssm", "rglru", "encdec", "thirdparty")
SLOW_FAMILIES = ("moe-topk",)        # kimi-k2 class: top-k>1 routing
MODES = ("kill", "reslot", "shrink", "commit", "swap")


@dataclass(frozen=True)
class Cell:
    family: str
    mode: str
    backend: str

    @property
    def id(self) -> str:
        return f"{self.family}×{self.mode}×{self.backend}"


def _backend_for(mode: str, backend: str) -> str:
    return "localfs↔sharded" if mode == "swap" else backend


def fast_cells() -> List[Cell]:
    # one targeted degradation cell rides in the fast tier: the
    # replicated sharded package loses a peer and the restore must read
    # around it. Degradation is a store property, not a family
    # property, so the full family×degraded product would be
    # redundant — one family stands in for all of them. The mid-chain
    # new-entry cell is a chain-shape property — an app whose semantic
    # state grows mid-run, so an entry's first appearance is a non-base
    # delta link that the restore must handle. And one churn-grow cell:
    # shrink-then-grow through the supervisor
    # is a *sequencing* property of the restore primitive (elastic
    # re-shard both directions), not a family property — one family
    # stands in for all of them
    return [Cell(f, m, _backend_for(m, "localfs"))
            for f in FAMILIES for m in MODES] \
        + [Cell("attention", "degraded", "sharded"),
           Cell("dynamic-entry", "midchain", "localfs"),
           Cell("attention", "churn-grow", "localfs")]


def slow_cells() -> List[Cell]:
    cells = [Cell(f, m, "sharded")
             for f in FAMILIES for m in MODES if m != "swap"]
    cells += [Cell(f, m, _backend_for(m, "localfs"))
              for f in SLOW_FAMILIES for m in MODES]
    return cells
