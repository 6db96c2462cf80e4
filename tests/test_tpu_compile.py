"""AOT compiles of the main-path Pallas kernels for a described v5e chip.

The TPU compiler is installed here and compiles for a chip that is
described, not attached: a kernel that Mosaic refuses (unaligned
slices, too much VMEM) fails here, at no chip time. Each compile must
keep its ``tpu_custom_call`` — the kernel ran, not a fallback.

The topology is described only inside the module fixture: one process
at a time may load libtpu, so nothing here touches it at import.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from slate_tpu.ops import pallas_ops


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off. The chip
    # has no f64, so compile as the chip runs: without conftest's x64.
    was = (jax.config.jax_enable_compilation_cache,
           jax.config.jax_enable_x64)
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_enable_x64", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was[0])
    jax.config.update("jax_enable_x64", was[1])


def _hlo(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in shapes]
    return fn.lower(*args).compile().as_text()


@pytest.mark.parametrize("kernel,shape", [
    ("chol_tile", (256, 256)),
    ("lu_panel_base", (8192, 32)),
    ("qr_panel_base", (8192, 32)),
    ("qr_panel_base_wide", (2048, 128)),
    pytest.param("chol_tile", (1024, 1024), marks=pytest.mark.slow),
])
def test_kernel_compiles_for_v5e(one_chip, kernel, shape):
    assert "tpu_custom_call" in _hlo(getattr(pallas_ops, kernel),
                                     one_chip, shape)


def test_posv_program_compiles_with_kernels(one_chip, monkeypatch):
    """A whole small posv program: the backend query is steered to
    "tpu" so the Pallas gates open for the described chip."""
    import slate_tpu as st

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    n, nb = 512, 256
    A = st.TiledMatrix(jax.ShapeDtypeStruct((n, n), jnp.float32,
                                            sharding=one_chip),
                       n, n, nb, st.MatrixKind.Hermitian, st.Uplo.Lower)
    B = st.TiledMatrix(jax.ShapeDtypeStruct((n, nb), jnp.float32,
                                            sharding=one_chip),
                       n, 4, nb)
    hlo = jax.jit(st.posv).lower(A, B).compile().as_text()
    assert hlo.count("tpu_custom_call") >= n // nb


@pytest.mark.parametrize("m", [4096, 8192])
def test_geqrf_program_keeps_panel_kernels(one_chip, monkeypatch, m):
    """Inside a whole geqrf program a QR base takes more scoped VMEM
    than alone: a (7968, 32) base overran Mosaic's default 16 MiB in a
    gels program. The panel kernels raise the limit, so panels up to
    the 8192·32 cells budget keep their kernel."""
    import slate_tpu as st

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    n = nb = 64
    A = st.TiledMatrix(jax.ShapeDtypeStruct((m, n), jnp.float32,
                                            sharding=one_chip), m, n, nb)
    hlo = jax.jit(lambda A: st.geqrf(A).vr).lower(A).compile().as_text()
    assert "tpu_custom_call" in hlo


@pytest.mark.slow
def test_gels_program_compiles_with_kernels(one_chip, monkeypatch):
    """The program where a (7968, 32) QR base overran the default
    scoped VMEM (~60 s to compile)."""
    import slate_tpu as st

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    m, n, nb = 8192, 2048, 512

    def sds(rows, cols):
        return jax.ShapeDtypeStruct((rows, cols), jnp.float32,
                                    sharding=one_chip)
    A = st.TiledMatrix(sds(m, n), m, n, nb)
    B = st.TiledMatrix(sds(m, nb), m, 16, nb)
    hlo = jax.jit(st.gels).lower(A, B).compile().as_text()
    assert "tpu_custom_call" in hlo
