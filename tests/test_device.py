"""Process-level device policy (repro.device): the persistent compile
cache's one directory, and the refusal to spawn engine-building
children under a parent that holds a TPU. The TPU cases steer
``jax.default_backend`` in the test; no chip is involved."""
import multiprocessing

import jax
import pytest

from repro.core.campaign import CampaignExecutor, ExecutorConfig
from repro.core.engine import EngineConfig
from repro.device import (CACHE_ENV, CHECKOUT_ROOT, DEFAULT_CACHE_DIR,
                          ChipOwnedError, ensure_compile_cache)


@pytest.fixture
def cache_dir_config():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_leaves_env_dir_to_jax(monkeypatch, tmp_path,
                                             cache_dir_config):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(CACHE_ENV, str(tmp_path))
    assert ensure_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_fixed_checkout_dir(monkeypatch,
                                                      cache_dir_config):
    monkeypatch.delenv(CACHE_ENV, raising=False)
    path = ensure_compile_cache()
    assert path == str(DEFAULT_CACHE_DIR) == ensure_compile_cache()
    assert jax.config.jax_compilation_cache_dir == path
    assert DEFAULT_CACHE_DIR == CHECKOUT_ROOT / ".jax_cache"
    assert (CHECKOUT_ROOT / "src" / "repro" / "device.py").is_file()


@pytest.fixture
def on_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


@pytest.mark.parametrize("runtime", ["process", "fabric"])
def test_worker_runtimes_refuse_to_spawn_under_a_chip(corpus, ft_router,
                                                      on_tpu, runtime):
    ccfg, docs = corpus
    before = len(multiprocessing.active_children())
    ex = CampaignExecutor(EngineConfig(alpha=0.1, batch_size=16),
                          ExecutorConfig(n_nodes=2, runtime=runtime),
                          ft_router, ccfg)
    with pytest.raises(ChipOwnedError, match="queue 2 item 1"):
        ex.run(docs[75:107])
    assert len(multiprocessing.active_children()) == before


@pytest.mark.parametrize("flag", ["--workers", "--fabric-workers"])
def test_serve_reports_chip_ownership_as_argparse_error(on_tpu, capsys,
                                                        cache_dir_config,
                                                        flag):
    from repro.launch.serve import main as serve_main

    with pytest.raises(SystemExit) as e:
        serve_main([flag, "2"])
    assert e.value.code == 2
    err = capsys.readouterr().err
    assert "already holds the TPU" in err and "queue 2 item 1" in err


def test_router_config_choice(capsys, cache_dir_config):
    from repro.launch.serve import main as serve_main, router_encoder_config

    pub = router_encoder_config("published")
    assert (pub.n_layers, pub.d_model, pub.max_len, pub.vocab_size) == (
        12, 768, 512, 31090)
    red = router_encoder_config()
    assert (red.n_layers, red.d_model) == (2, 32)
    with pytest.raises(ValueError, match="router config"):
        router_encoder_config("large")
    with pytest.raises(SystemExit) as e:
        serve_main(["--router-config", "published"])
    assert e.value.code == 2
    assert "only the llm variant" in capsys.readouterr().err
