"""The persistent compiled-program cache index.

The compiled executables themselves live in the engine's program caches
(``fed.engine``'s ``lru_cache``'d program constructors + each program's
jit cache, keyed on static config × abstract argument signature) — jax
already guarantees that dispatching a previously-seen shape skips
compilation entirely.  What the engine layer does *not* know is the
serving question: **will this admission compile or not, and how often do
we win?**  :class:`ProgramCache` is that index: it tracks every
:func:`~repro.api.lowering.program_key` ever dispatched and classifies
each admission warm (all of its chunk programs seen before → zero new
``TraceEvent``s in the PR-6 ledger, test-enforced) or cold, feeding the
hit/miss counters ``ServiceStats`` reports.

The registry is class-shared by default, so every service instance in a
process sees programs warmed by any other — a restarted service object
re-admits known shapes warm because the jit caches it fronts are
process-level too.  Compiled executables persist across processes only
through jax's own compilation cache, placed once per entry point by
:func:`repro.launch.compile_cache.use_compile_cache`; a fresh process
still re-traces a known shape before consulting it, so the registry
deliberately stays process-scoped (pre-marking disk-cached keys warm
would break the warm ⇒ zero-``TraceEvent`` contract).
"""
from __future__ import annotations

from typing import Dict, Iterable, Tuple

__all__ = ["ProgramCache"]


class ProgramCache:
    """Hit/miss index over dispatched program shapes (see module doc)."""

    _SHARED: Dict[tuple, int] = {}

    def __init__(self, shared: bool = True):
        self._seen: Dict[tuple, int] = (ProgramCache._SHARED if shared
                                        else {})
        self.hits = 0
        self.misses = 0

    def __len__(self) -> int:
        return len(self._seen)

    def __contains__(self, key: tuple) -> bool:
        return key in self._seen

    def admit(self, keys: Iterable[tuple]) -> Tuple[int, int]:
        """Record one admission's program keys; returns ``(hits,
        misses)`` over the keys (a key both looked up and inserted here
        counts once).  ``misses == 0`` is the *warm admission* contract:
        every program this bucket will dispatch has already been traced
        and compiled in this process, so running it must add zero
        ``TraceEvent``s to the engine ledger."""
        hits = misses = 0
        for key in keys:
            if key in self._seen:
                self._seen[key] += 1
                hits += 1
            else:
                self._seen[key] = 1
                misses += 1
        self.hits += hits
        self.misses += misses
        return hits, misses

    def use_count(self, key: tuple) -> int:
        """How many admissions have dispatched ``key`` (0 = never)."""
        return self._seen.get(key, 0)

    @classmethod
    def clear_shared(cls) -> None:
        """Drop the process-shared registry (tests only — the jit caches
        it fronts are NOT cleared, so a cleared index under-reports
        warmth but never breaks the warm ⇒ no-trace contract)."""
        cls._SHARED.clear()
