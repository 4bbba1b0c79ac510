"""Compile guards for the chip: the campaign's main-path kernels and the
published-width route step, compiled (``interpret=False``) for a
described TPU v5e chip. Nothing runs and no chip is needed — the TPU
compiler refuses here what it would refuse on the chip (illegal block
tiling, unlowerable primitives, too much VMEM or HBM).

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library, and under pytest-
xdist every worker imports every test file. Keep these tests in this
one file so that one worker loads it.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.budget_route.kernel import budget_route_kernel
from repro.kernels.budget_route.ops import capacity_floor
from repro.kernels.fast_features.kernel import fast_features_kernel
from repro.kernels.ngram_score.kernel import ngram_bleu_kernel

HBM_BYTES = 16 * 1024 ** 3                 # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # else libtpu logs to /tmp
        from jax.experimental import topologies
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means no TPU
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A described-chip compile written to the persistent cache cannot
    be read back without the chip: keep the cache off around it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < HBM_BYTES


@pytest.mark.parametrize("n,d", [(256, 512), (65536, 512), (256, 64)],
                         ids=["route_batch", "route_64k", "reduced_width"])
def test_budget_route_compiles_for_v5e(one_chip, no_compile_cache, n, d):
    compiled = budget_route_kernel.lower(
        _sds((n,), jnp.float32, one_chip), _sds((n, d), jnp.int32, one_chip),
        _sds((), jnp.float32, one_chip), capacity=capacity_floor(0.05, n),
        interpret=False).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("max_len", [0, 512])
def test_fast_features_compiles_for_v5e(one_chip, no_compile_cache,
                                        max_len):
    n, width = 256, 2048
    vec = _sds((n,), jnp.int32, one_chip)
    compiled = fast_features_kernel.lower(
        _sds((n, width), jnp.int32, one_chip), vec, vec, vec, vec,
        max_len=max_len, block_l=128, ws=2, scramble=3, mangled=4,
        latex_lo=8010, ident_lo=8510, interpret=False).compile()
    _assert_kernel(compiled)


def test_ngram_score_compiles_for_v5e(one_chip, no_compile_cache):
    b, max_len = 256, 256
    ids = _sds((b, max_len), jnp.int32, one_chip)
    lens = _sds((b,), jnp.int32, one_chip)
    compiled = ngram_bleu_kernel.lower(ids, ids, lens, lens,
                                       max_len=max_len,
                                       interpret=False).compile()
    _assert_kernel(compiled)


def test_published_route_step_compiles_with_kernel(one_chip,
                                                   no_compile_cache,
                                                   monkeypatch):
    """The jitted route step at the published adaparse-router widths
    (12 layers, d=768, 512 tokens) on the App. C batch k=256, with the
    budget_route kernel in it. Dispatch asks ``jax.default_backend()``,
    which still says cpu here, so the test steers it to the TPU branch."""
    from repro.common import unwrap
    from repro.core.router import make_route_step
    from repro.launch.serve import router_encoder_config
    from repro.models import encoder as enc_lib

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = router_encoder_config("published")
    assert (cfg.n_layers, cfg.d_model, cfg.max_len) == (12, 768, 512)
    params = jax.tree_util.tree_map(
        lambda a: _sds(a.shape, a.dtype, one_chip),
        jax.eval_shape(lambda: unwrap(enc_lib.init_encoder(cfg, 0))))
    b = 256
    compiled = jax.jit(make_route_step(cfg, 0.05)).lower(
        params, _sds((b, cfg.max_len), jnp.int32, one_chip),
        _sds((b, cfg.max_len), jnp.float32, one_chip),
        _sds((b,), jnp.float32, one_chip)).compile()
    _assert_kernel(compiled)
