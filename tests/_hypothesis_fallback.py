"""Fallback stand-ins for hypothesis when it is not installed.

Tier-1 must collect and run without optional dev deps (ROADMAP). Test modules
do ``from _hypothesis_fallback import given, settings, st`` (and
``example``, where they pin inputs) inside the
``except ImportError`` arm of their hypothesis import; property-based tests
then collect as zero-argument functions that skip with a clear reason, while
every non-property test in the module still runs.
"""

from __future__ import annotations

import pytest


def given(*_args, **_kwargs):
    def deco(fn):
        def skipper():
            pytest.skip("hypothesis not installed (see requirements-dev.txt)")

        skipper.__name__ = fn.__name__
        skipper.__doc__ = fn.__doc__
        return skipper

    return deco


def settings(*_args, **_kwargs):
    def deco(fn):
        return fn

    return deco


example = settings


class _AnyStrategy:
    """Accepts any strategy constructor call; values are never drawn."""

    def __getattr__(self, _name):
        def strategy(*_args, **_kwargs):
            return None

        return strategy


st = _AnyStrategy()
