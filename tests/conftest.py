"""Shared test config: make the tests directory importable so the
``_hypothesis_fallback`` shim resolves regardless of pytest rootdir, and
the repository root so tests can read the ``benchmarks`` expectations."""
import os
import sys

_TESTS = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _TESTS)
sys.path.insert(1, os.path.dirname(_TESTS))
