"""Ahead-of-time compiles for a TPU v5e that is described, not attached.

Each Pallas kernel at float32 (the dtype the kernels serve on the chip, see
``kernels/ops.py``) at the widths ``chip_smoke.py`` drives, and the default
rounds program, compiled by the TPU compiler that ships with jaxlib.  A
compile that passes is not a chip run; it catches what the Mosaic and XLA
TPU compilers refuse (unaligned slices, unsupported primitives, 64-bit
index arithmetic, VMEM overflow) at no chip time.

The topology is described inside a module-scoped fixture (never at import,
in a ``skipif`` or in a ``parametrize``): only one process at a time may
load the TPU library, and only the test worker that runs this file does.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import cameo
from repro.kernels import ops
from repro.kernels.acf_impact import acf_impact_pallas
from repro.kernels.acf_window_impact import acf_window_impact_pallas
from repro.kernels.fused_round import prefix_devs_pallas, window_rows_pallas
from repro.kernels.lag_dot import lag_dot_pallas

F32, I32 = jnp.float32, jnp.int32

# (series, L, kappa, bucket): the aggregate-series buckets of the three lag
# depths chip_smoke.py runs -- uk_elec (L=48), min_temp (L=365, the widest
# lag) and solar (L=24 on kappa=120 aggregates).
CASES = {
    "uk_elec": (48, 1, 18432),
    "min_temp": (365, 1, 3840),
    "solar": (24, 120, 1048680),
}


def _widths(kappa: int, nb: int):
    """Tier capacities and window widths of the rounds program (the
    ``_round_fns`` sizing at the default ``window=64, alpha=0.1``)."""
    W, WB = 64, 8
    wy = (lambda w: w) if kappa == 1 else (lambda w: w // kappa + 2)
    return dict(
        ny=nb // kappa,
        tier_b=(min(nb, max(24, nb // 24)), wy(WB)),
        tier_c=(min(nb, max(16, nb // 48)), wy(W)),
        k_max=(int(0.1 * nb), wy(W)),
        chunk=(4096, wy(W)))


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    one = SingleDeviceSharding(topo.devices[0])

    def shape(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)
    return shape


@pytest.fixture(scope="module", autouse=False)
def no_persistent_cache():
    """A compile for a described device is written to the persistent cache
    but cannot be read back without the chip; keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


KERNELS = ("lag_dot", "window_rows_b", "window_rows_c", "prefix_devs",
           "acf_window_impact", "acf_impact")


@pytest.mark.parametrize("kernel", KERNELS)
@pytest.mark.parametrize("series", sorted(CASES))
def test_kernel_compiles_for_v5e(spec, no_persistent_cache, series, kernel):
    L, kappa, nb = CASES[series]
    w = _widths(kappa, nb)
    ny = w["ny"]
    agg, p0 = spec((5, L), F32), spec((L,), F32)
    if kernel == "lag_dot":
        _compile(functools.partial(lag_dot_pallas, L=L), spec((ny,), F32))
    elif kernel.startswith("window_rows"):
        K, Wy = w["tier_b" if kernel.endswith("b") else "tier_c"]
        _compile(functools.partial(window_rows_pallas, L=L),
                 spec((ny,), F32), spec((K, Wy), F32), spec((K,), I32), agg,
                 spec((), I32))
    elif kernel == "prefix_devs":
        K, Wy = w["k_max"]
        _compile(functools.partial(prefix_devs_pallas, L=L, greedy=True),
                 spec((ny,), F32), spec((K, Wy), F32), spec((K,), I32),
                 spec((K,), jnp.bool_), agg, p0, spec((), I32),
                 spec((), F32))
    elif kernel == "acf_window_impact":
        P, Wy = w["chunk"]
        _compile(functools.partial(acf_window_impact_pallas, ny=ny, L=L),
                 spec((P, Wy + 2 * L), F32), spec((P, Wy), F32),
                 spec((P,), I32), agg, p0)
    else:
        _compile(functools.partial(acf_impact_pallas, L=L),
                 spec((ny,), F32), spec((ny,), F32), agg, p0)


@pytest.mark.parametrize("dtype,backend", [("float32", "pallas"),
                                           ("float64", "auto")])
def test_rounds_program_compiles_with_kernel(spec, no_persistent_cache,
                                             monkeypatch, dtype, backend):
    """The whole rounds program at the uk_elec bucket: every kernel at
    float32 with ``backend="pallas"``, and the default float64
    configuration, whose ranking kernel runs at float32."""
    monkeypatch.delenv("CAMEO_BACKEND", raising=False)
    monkeypatch.delenv("CAMEO_FORCE_INTERPRET", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(ops, "interpret_mode", lambda: False)
    assert ops.resolve_backend(backend) == "pallas"
    L, _, nb = CASES["uk_elec"]
    cfg = cameo.CameoConfig(eps=1e-2, lags=L, dtype=dtype, backend=backend)
    dt = cfg.jdtype()
    assert cameo._round_bucket(17520, cfg) == nb
    compiled = cameo._rounds_padded.lower(
        spec((nb,), dt), spec((), I32), spec((), I32), spec((), dt),
        cfg=cfg).compile()
    assert "tpu_custom_call" in compiled.as_text()
