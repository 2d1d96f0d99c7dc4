"""Faults planted in the timed path, for the readings that the check's
limits are set from (``control.py``) and for the tests that see ``correct``
come out false (``test_bench_check.py``).

Each wraps the decode step of the served bundles, so that the window's
stages and the check's replay of them both run the broken step:

- ``state_unchanged``: the decode step returns the cache it was given, so
  the keys and values of the generated tokens are never stored;
- ``token_altered``: query 0 emits token 5 at every decode step.
"""
from __future__ import annotations

FAULTS = ("state_unchanged", "token_altered")


def _broken(decode, fault: str):
    def step(params, token, cache, pos):
        logits, new_cache = decode(params, token, cache, pos)
        if fault == "token_altered":
            return logits.at[0, :, 5].add(1e4), new_cache
        return logits, cache

    return step


def plant(bundles: dict, fault: str) -> None:
    """Break the decode step of every bundle in ``bundles`` by ``fault``."""
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r} (have {FAULTS})")
    for b in bundles.values():
        b.decode = _broken(b.decode, fault)
