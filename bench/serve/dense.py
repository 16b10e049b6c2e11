"""Dense unit rows on the service: :mod:`bench.prefill` builds and fills it,
and ``submit`` takes a slice of the ``(n, d)`` pool."""

from __future__ import annotations

from bench.prefill import build_service, install

__all__ = ["build_service", "install", "submit_args"]


def submit_args(rows, a: int, b: int) -> tuple:
    return (rows[a:b],)
