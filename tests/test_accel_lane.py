"""The real-hardware Pallas lane (DESIGN.md §13): unit tests for
``kernels.ops.default_interpret`` — the env-var override and platform
auto-detect that decide whether Pallas kernels interpret (CPU) or compile
(Mosaic on TPU). The compiled kernels themselves are checked by
tests/test_tpu_compile.py (compiled for a described v5e chip) and on the
chip by chip_smoke.py.
"""
import jax
import numpy as np
import pytest

from repro.kernels.ops import _ACCEL_PLATFORMS, default_interpret

# -- default_interpret resolution --------------------------------------------


@pytest.mark.parametrize("val", ["0", "false", "OFF", " no "])
def test_env_forces_compiled(monkeypatch, val):
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", val)
    assert default_interpret() is False


@pytest.mark.parametrize("val", ["1", "true", "on", "yes"])
def test_env_forces_interpreter(monkeypatch, val):
    # explicit ON beats platform detect — debugging on a TPU host
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", val)
    assert default_interpret() is True


@pytest.mark.parametrize("val", [None, "", "  "])
def test_unset_or_blank_falls_back_to_platform(monkeypatch, val):
    if val is None:
        monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    else:
        monkeypatch.setenv("REPRO_PALLAS_INTERPRET", val)
    assert default_interpret() is (jax.default_backend()
                                   not in _ACCEL_PLATFORMS)


def test_explicit_arg_still_overrides(monkeypatch, tiny_scene):
    """Per-call interpret= beats both env and platform (ops docstring)."""
    from repro.kernels.ops import sort_groups_bitonic

    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    import jax.numpy as jnp

    keys = jnp.array([[3.0, 1.0, 2.0, jnp.inf]], jnp.float32)
    idx = jnp.array([[0, 1, 2, 3]], jnp.int32)
    # interpret=True must run fine on CPU even with the env forcing compiled
    k, v = sort_groups_bitonic(keys, idx, interpret=True)
    assert np.asarray(k)[0, 0] == 1.0
    assert list(np.asarray(v)[0, :3]) == [1, 2, 0]


def test_env_interpreter_on_chip_warns(monkeypatch):
    """Forcing the interpreter on a TPU is allowed for debugging, but never
    silent: a chip run must not drop to interpret mode unnoticed."""
    from repro.kernels import ops

    monkeypatch.setattr(ops.jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    assert default_interpret() is False
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    with pytest.warns(RuntimeWarning, match="interpreter"):
        assert default_interpret() is True
