"""Baseline suppression file — "no new findings" CI gating.

The committed baseline (``analysis-baseline.json`` at the repo root)
records the *intentional* exceptions: findings the team has looked at and
decided to keep, each with a mandatory human-readable ``reason``. CI runs
``python -m repro.analysis src/ --fail-on-new`` — a finding matching a
suppression is reported as baselined and does not fail the build; any
gating finding without a matching entry does.

Matching is on ``(code, file, obj)`` — deliberately line-insensitive (an
edit above the finding must not un-suppress it) and obj-sensitive (a
second function growing the same defect is a *new* finding). ``obj: "*"``
matches any object in the file, for whole-file waivers.

Schema::

    {"schema": 1,
     "suppressions": [
       {"code": "PAL004", "file": "src/repro/kernels/gather_spmm.py",
        "obj": "ell_spmm", "reason": "..."}]}

Unused suppressions (no finding matched) are reported so the baseline
can't silently rot after a fix lands.
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Optional

from repro.analysis.findings import CODES, Finding

__all__ = ["Suppression", "Baseline", "load_baseline", "write_baseline",
           "DEFAULT_BASELINE"]

DEFAULT_BASELINE = "analysis-baseline.json"


@dataclasses.dataclass(frozen=True)
class Suppression:
    code: str
    file: str
    obj: str
    reason: str

    def matches(self, f: Finding) -> bool:
        return (self.code == f.code and self.file == f.file
                and self.obj in ("*", f.obj))


@dataclasses.dataclass
class Baseline:
    suppressions: list[Suppression]
    path: Optional[str] = None

    def split(self, findings: list[Finding]
              ) -> tuple[list[Finding], list[Finding], list[Suppression]]:
        """(new, suppressed, unused-suppressions). Only gating findings
        (error/warning) participate; info findings are never "new"."""
        used: set[Suppression] = set()
        new, suppressed = [], []
        for f in findings:
            if not f.gating:
                continue
            hit = next((s for s in self.suppressions if s.matches(f)), None)
            if hit is None:
                new.append(f)
            else:
                used.add(hit)
                suppressed.append(f)
        unused = [s for s in self.suppressions if s not in used]
        return new, suppressed, unused


def load_baseline(path: str) -> Baseline:
    if not os.path.exists(path):
        return Baseline(suppressions=[], path=path)
    with open(path) as f:
        raw = json.load(f)
    assert raw.get("schema") == 1, f"unknown baseline schema in {path}"
    sups = []
    for s in raw.get("suppressions", []):
        assert s.get("reason"), \
            f"baseline entry {s} needs a reason string ({path})"
        assert s["code"] in CODES, \
            f"baseline entry {s} names unregistered code ({path})"
        sups.append(Suppression(code=s["code"], file=s["file"],
                                obj=s.get("obj", "*"), reason=s["reason"]))
    return Baseline(suppressions=sups, path=path)


def write_baseline(path: str, findings: list[Finding], *,
                   reason: str = "baselined by --write-baseline; "
                                 "review and replace with a real reason"
                   ) -> Baseline:
    """Snapshot every current gating finding as a suppression. Meant as a
    bootstrap: each generated entry carries the placeholder reason until a
    human replaces it."""
    seen: set[tuple] = set()
    sups = []
    for f in findings:
        if not f.gating or f.key() in seen:
            continue
        seen.add(f.key())
        sups.append({"code": f.code, "file": f.file, "obj": f.obj,
                     "reason": reason})
    with open(path, "w") as fh:
        json.dump({"schema": 1, "suppressions": sups}, fh, indent=2)
        fh.write("\n")
    return load_baseline(path)
