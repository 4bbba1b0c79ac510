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
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.budget_route.kernel import budget_route_kernel
from repro.kernels.budget_route.ops import capacity_floor
from repro.kernels.encoder_attention.kernel import encoder_attention_kernel
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


def test_encoder_attention_compiles_for_v5e(one_chip, no_compile_cache):
    """The route step's attention at the router's shape: 256 documents,
    12 heads of 64 over 512 positions."""
    b, s, h, d = 256, 512, 12, 64
    qkv = _sds((b, s, h, d), jnp.bfloat16, one_chip)
    compiled = encoder_attention_kernel.lower(
        qkv, qkv, qkv, _sds((b, s), jnp.float32, one_chip),
        interpret=False).compile()
    _assert_kernel(compiled)


def _route_step(which: str, b: int, one_chip, monkeypatch):
    """The jitted route step of the ``which`` router encoder on a batch
    of ``b``, compiled for the described chip. Dispatch asks
    ``jax.default_backend()``, which still says cpu here, so the step is
    steered to its TPU branch (the kernels)."""
    from repro.common import unwrap
    from repro.core.router import make_route_step
    from repro.launch.serve import router_encoder_config
    from repro.models import encoder as enc_lib

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = router_encoder_config(which)
    params = jax.tree_util.tree_map(
        lambda a: _sds(a.shape, a.dtype, one_chip),
        jax.eval_shape(lambda: unwrap(enc_lib.init_encoder(cfg, 0))))
    step = make_route_step(cfg, 0.05)
    assert step.attention_kernel
    compiled = jax.jit(step).lower(
        params, _sds((b, cfg.max_len), jnp.int32, one_chip),
        _sds((b, cfg.max_len), jnp.float32, one_chip),
        _sds((b,), jnp.float32, one_chip)).compile()
    return cfg, compiled


def _hlo_computation(hlo: str, name: str) -> str:
    """The body of the HLO computation ``name``."""
    (body,) = re.findall(rf"^%{re.escape(name)} [^\n]*\{{\n(.*?)^\}}",
                         hlo, re.M | re.S)
    return body


def _hlo_defines(hlo: str, name: str) -> str:
    """The line that defines the instruction ``name``."""
    (line,) = re.findall(rf"^\s*(?:ROOT )?{re.escape(name)} = .*$", hlo, re.M)
    return line


def _fused_ops(hlo: str, line: str) -> str:
    """Every instruction a fusion runs, through nested fusions."""
    bodies, todo = [], re.findall(r"calls=%([\w.\-]+)", line)
    while todo:
        body = _hlo_computation(hlo, todo.pop())
        bodies.append(body)
        todo += re.findall(r"calls=%([\w.\-]+)", body)
    return "\n".join(bodies)


@pytest.mark.parametrize("b", [32, 256])
def test_reduced_route_step_compiles_with_kernel(one_chip, no_compile_cache,
                                                 monkeypatch, b):
    """``serve``'s default router (2 layers, d=32, 4 heads of 8 over 64
    tokens, f32) takes the attention kernel on a TPU too: its 8-lane head
    slices and K=8 contractions compile for the chip."""
    cfg, compiled = _route_step("reduced", b, one_chip, monkeypatch)
    assert (cfg.n_heads, cfg.d_model // cfg.n_heads, cfg.max_len) == \
        (4, 8, 64)
    _assert_kernel(compiled)
    assert "encoder_attention_kernel" in compiled.as_text()


def test_published_route_step_compiles_with_kernel(one_chip,
                                                   no_compile_cache,
                                                   monkeypatch):
    """The jitted route step at the published adaparse-router widths
    (12 layers, d=768, 512 tokens) on the App. C batch k=256, with the
    encoder attention and budget_route kernels in it. The layers' f32
    score tensor, (k, 12, 512, 512), no longer goes to HBM: the step's
    temporaries are under half of one such tensor (they were 4.03 GB
    with it)."""
    b = 256
    cfg, compiled = _route_step("published", b, one_chip, monkeypatch)
    assert (cfg.n_layers, cfg.d_model, cfg.max_len) == (12, 768, 512)
    _assert_kernel(compiled)
    hlo = compiled.as_text()
    call = re.search(r"%(encoder_attention_kernel[.\d]*) = \S+ "
                     r"custom-call\(([^)]*)\), custom_call_target="
                     r"\"tpu_custom_call\"", hlo)
    assert call
    # the q/k/v projections write the rows the kernel reads, and `wo`
    # reads the rows it writes: no copy or transpose of a (k, 512, 768)
    # tensor around the call, in those fusions or between them and it
    rows = f"bf16[{b},{cfg.max_len},{cfg.d_model}]"
    q, k, v, _ = call.group(2).split(", ")
    for operand in (q, k, v):
        line = _hlo_defines(hlo, operand)
        assert line.split(" = ")[1].startswith(rows) and " fusion(" in line
        ops = _fused_ops(hlo, line)
        assert "bsd,dhk->bshk/dot_general" in ops, line
        assert not re.search(r" (copy|transpose)\(", ops), line
    (user,) = [line for line in hlo.splitlines()
                if re.search(rf"[(, ]%{re.escape(call.group(1))}[,)]", line)]
    assert " fusion(" in user
    ops = _fused_ops(hlo, user)
    assert "bsm,md->bsd/dot_general" in ops, user
    assert not re.search(r" (copy|transpose)\(", ops), user
    scores = b * cfg.n_heads * cfg.max_len ** 2 * 4
    assert compiled.memory_analysis().temp_size_in_bytes < scores / 2


def _nougat_program(which: str, one_chip, span: int | None = None,
                    monkeypatch=None):
    """``parse_encode`` (a chunk of pages into the slot caches) or, with
    ``span``, ``parse_decode`` (a greedy step reading ``span`` cache
    positions) at nougat-base's published widths, compiled for the
    described chip. With ``monkeypatch`` the decode is steered to its
    TPU branch (the decode attention kernel), as ``_route_step`` is."""
    from repro.core.parser_model import parser_config
    from repro.models import nougat

    if monkeypatch is not None:
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = parser_config("published")
    on_chip = lambda t: jax.tree_util.tree_map(          # noqa: E731
        lambda a: _sds(a.shape, a.dtype, one_chip), t)
    params = on_chip(jax.eval_shape(lambda: nougat.init_params(cfg, 0)))
    caches = on_chip(jax.eval_shape(lambda: nougat.slot_caches(cfg)))
    encode, decode = nougat.serving_programs(cfg)
    step = _sds((), jnp.int32, one_chip)
    if span is None:
        images = _sds((nougat.ENCODE_CHUNK,) + cfg.image_hw, jnp.uint8,
                      one_chip)
        return cfg, encode.lower(params, images, caches["xk"], caches["xv"],
                                 step).compile()
    state = on_chip(jax.eval_shape(lambda: nougat.decode_state(
        cfg, nougat.slot_caches(cfg), [0] * cfg.decode_slots, [0] * 16,
        [-1] * 16)))
    return cfg, decode.lower(params, state, caches["xk"], caches["xv"],
                             step, span=span).compile()


def test_nougat_encode_compiles_at_published_widths(one_chip,
                                                    no_compile_cache):
    """nougat-base's encoder (896x672 pages, Swin 2/2/14/2) on a chunk of
    8 pages into the 96 slots' cross-attention K/V, which it updates in
    place: the caches' 2.3 GB are aliased, not copied."""
    cfg, compiled = _nougat_program("encode", one_chip)
    assert (cfg.image_hw, cfg.depths, cfg.enc_tokens) == \
        ((896, 672), (2, 2, 14, 2), 588)
    mem = compiled.memory_analysis()
    cross = 2 * cfg.dec_layers * cfg.decode_slots * cfg.enc_tokens \
        * cfg.dec_d_model * 2
    assert mem.alias_size_in_bytes >= cross
    print(f"parse_encode: temp_size_in_bytes {mem.temp_size_in_bytes}")
    assert mem.temp_size_in_bytes < 4 * 1024 ** 3
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes) < HBM_BYTES


@pytest.mark.parametrize("span", [128, 896])
def test_nougat_decode_compiles_at_published_widths(one_chip,
                                                    no_compile_cache,
                                                    monkeypatch, span):
    """A decode step of nougat-base (10 layers, d 1024, 50,000 tokens)
    over 96 slots, as a TPU runs it: each layer's self and cross
    attention is the decode attention kernel, which reads the caches
    where they lie: no copy or transpose of a self-attention block
    (96 x 128 x 1024) or a cross-attention K/V (96 x 592 x 1024) feeds
    it. The self-attention cache is updated in place, the temporaries
    stay far below one copy of it (3.5 GB), and the cross-attention K/V
    (2.3 GB) is read, not copied."""
    from repro.models import nougat

    cfg, compiled = _nougat_program("decode", one_chip, span, monkeypatch)
    hlo = compiled.as_text()
    calls = re.findall(r"%(decode_attention_kernel[.\d]*) = \S+ custom-call"
                       r"\(([^)]*)\), custom_call_target=\"tpu_custom_call\"",
                       hlo)
    assert len(calls) == 2 * cfg.dec_layers
    caches = [rf"bf16\[{cfg.decode_slots},{rows},{cfg.dec_d_model}\]"
              for rows in (nougat.KV_BLOCK, nougat.cross_rows(cfg))]
    relaid = re.compile(rf"= (?:{'|'.join(caches)})\S* (copy|transpose)\(")
    for _, operands in calls:
        kv = {op for op in re.sub(r"/\*[^*]*\*/", "", operands).split(", ")
              if re.match("|".join(caches),
                          _hlo_defines(hlo, op).split(" = ")[1])}
        assert kv
        for op in kv:
            line = _hlo_defines(hlo, op)
            assert not relaid.search(line), line
            assert not relaid.search(_fused_ops(hlo, line)), line
    mem = compiled.memory_analysis()
    own = 2 * cfg.dec_layers * cfg.decode_slots * cfg.cache_len \
        * cfg.dec_d_model * 2
    assert mem.alias_size_in_bytes >= own
    print(f"parse_decode span {span}: temp_size_in_bytes "
          f"{mem.temp_size_in_bytes}")
    assert mem.temp_size_in_bytes < own / 4
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes) < HBM_BYTES
