"""Allowlist for vetted shotgun-lint exceptions (DESIGN §10).

``allowlist.toml`` holds one ``[[allow]]`` table per vetted finding:

    [[allow]]
    rule   = "SL001"                       # required: the rule id
    path   = "src/repro/launch/serve.py"   # required: repo-relative path
    match  = "time.time"                   # optional: message substring
    reason = "host-side queue timing, never traced"   # required

Matching is line-number-free on purpose — line anchors rot with every
edit.  A finding is suppressed when an entry's rule and path match and
``match`` (when present) is a substring of the message.  Entries that
suppress nothing are reported by the CLI so dead exceptions get pruned.
"""
from __future__ import annotations

import pathlib
import tomllib
from typing import Iterable, NamedTuple

from repro.analyze.findings import Finding


class AllowEntry(NamedTuple):
    rule: str
    path: str
    reason: str
    match: str = ""

    def covers(self, f: Finding) -> bool:
        return (f.rule == self.rule and f.path == self.path
                and (not self.match or self.match in f.message))


def load_allowlist(path: str | pathlib.Path | None) -> list[AllowEntry]:
    if path is None:
        return []
    path = pathlib.Path(path)
    if not path.exists():
        return []
    data = tomllib.loads(path.read_text())
    entries = []
    for i, raw in enumerate(data.get("allow", [])):
        missing = {"rule", "path", "reason"} - set(raw)
        if missing:
            raise ValueError(
                f"allowlist entry {i} missing required keys {sorted(missing)}")
        entries.append(AllowEntry(rule=raw["rule"], path=raw["path"],
                                  reason=raw["reason"],
                                  match=raw.get("match", "")))
    return entries


def apply_allowlist(findings: Iterable[Finding],
                    entries: list[AllowEntry]):
    """Split findings into (kept, suppressed); also returns the entries that
    matched nothing so the CLI can flag dead exceptions."""
    kept, suppressed = [], []
    used = [False] * len(entries)
    for f in findings:
        hit = False
        for i, e in enumerate(entries):
            if e.covers(f):
                used[i] = True
                hit = True
        (suppressed if hit else kept).append(f)
    unused = [e for i, e in enumerate(entries) if not used[i]]
    return kept, suppressed, unused
