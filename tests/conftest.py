"""Shared pytest wiring.

``--pallas-interpret`` forces the Pallas kernel dispatch on for the
whole test process (``repro.kernels.ops._use_pallas`` is patched to
return True): off a TPU, ``ops._pallas_interpret`` then routes every
kernel through interpret mode, so the whole suite — including the
serving engine's greedy decode — exercises the TPU kernel code paths
and must reproduce the reference results (the CI kernels-interpret job
runs the parity subset this way).
"""
import pytest


def pytest_addoption(parser):
    parser.addoption(
        "--pallas-interpret", action="store_true", default=False,
        help="dispatch to the Pallas kernels (interpret mode on CPU) "
             "for the whole test process")


@pytest.fixture(autouse=True)
def _pallas_dispatch(request, monkeypatch):
    if request.config.getoption("--pallas-interpret"):
        from repro.kernels import ops
        monkeypatch.setattr(ops, "_use_pallas", lambda: True)
