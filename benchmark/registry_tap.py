"""Reads the program's metrics registry by name, between two marks.

The program records counters and histograms (``distar_tpu.obs``); the
benchmark reads them, it does not own them. A mark remembers every counter's
value and every histogram's lifetime count, so that a reader gets a
counter's value at window open and a histogram's observations inside the
window, not the warm-up's.
"""
from __future__ import annotations

from typing import Dict, List, Optional


def _matches(key: tuple, labels: Dict[str, str]) -> bool:
    have = dict(key)
    return all(have.get(k) == v for k, v in labels.items())


class RegistryTap:
    def __init__(self, registry=None):
        if registry is None:
            from distar_tpu.obs import get_registry

            registry = get_registry()
        self._registry = registry
        self.marks: Dict[str, Dict] = {}

    def _series(self, name: str, labels: Optional[Dict[str, str]] = None):
        for fam in self._registry.collect():
            if fam["name"] == name:
                return [(k, inst) for k, inst in fam["series"] if _matches(k, labels or {})]
        return []

    def mark(self, label: str) -> None:
        snap = {}
        for fam in self._registry.collect():
            for key, inst in fam["series"]:
                snap[(fam["name"], key)] = (
                    inst.count if fam["type"] == "histogram" else inst.value)
        self.marks[label] = snap

    def value_at(self, mark: str, name: str, labels: Optional[Dict[str, str]] = None) -> Optional[float]:
        """A counter or gauge at a mark, summed over its label sets; None if
        it did not exist then."""
        snap = self.marks[mark]
        hits = [v for (n, key), v in snap.items() if n == name and _matches(key, labels or {})]
        return float(sum(hits)) if hits else None

    def observed_between(self, name: str, a: str, b: str,
                         labels: Optional[Dict[str, str]] = None) -> List[float]:
        """A histogram's observations between marks ``a`` and ``b`` (all
        matching label sets), as far as its reservoir still holds them."""
        out: List[float] = []
        for key, inst in self._series(name, labels):
            n_a = self.marks[a].get((name, key), 0)
            n_b = self.marks[b].get((name, key), 0)
            # the registry keeps the last ``maxlen`` observations and no
            # public way to read them: the reservoir is read directly
            with inst._lock:
                kept, total = list(inst._reservoir), inst._count
            first_kept = total - len(kept)
            out.extend(kept[max(n_a - first_kept, 0):max(n_b - first_kept, 0)])
        return out
