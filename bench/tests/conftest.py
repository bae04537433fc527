"""The benchmark's CPU tests: the program's global state kept apart
from other tests, and its tuning cache in a temporary file."""

import pytest


@pytest.fixture(autouse=True)
def _isolated_program_state(tmp_path, monkeypatch):
    from repro import obs
    from repro.core import set_gemm_fallback, set_gemm_mode
    from repro.tuning import registry

    monkeypatch.setenv("REPRO_TUNING_CACHE", str(tmp_path / "tuning.json"))
    monkeypatch.delenv("REPRO_AUTOTUNE", raising=False)
    monkeypatch.delenv("REPRO_TRACE", raising=False)
    set_gemm_mode("xla")
    set_gemm_fallback(False)
    registry.reset_registry()
    obs.reset_metrics()
    obs.disable_tracing()
    yield
    registry.reset_registry()
    obs.reset_metrics()
    obs.disable_tracing()
