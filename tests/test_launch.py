"""The launch surface around the serving path: engine builders, device
pinning, the compile cache, and what the launcher refuses or reports."""
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.config import ServingConfig, two_tier_topology
from repro.configs import reduced_config
from repro.launch import compile_cache, serve
from repro.serving.pool import build_engine_pools
from repro.serving.tiers import ServedResult, build_cluster_engines
from repro.serving.transport import (ProcessTransport, ReplicaSpec,
                                     TransportError)

SV = ServingConfig(max_batch=2, max_seq=64)


def _bf16_configs():
    return {"edge": reduced_config("qwen2-vl-2b").replace(dtype="bfloat16"),
            "cloud": reduced_config("qwen2.5-vl-7b").replace(
                dtype="bfloat16", num_layers=1)}


def test_builders_serve_explicit_configs_in_their_dtype():
    topo = two_tier_topology()
    cfgs = _bf16_configs()
    engines = build_cluster_engines(topo, SV, configs=cfgs)
    pools = build_engine_pools(topo, SV, configs=cfgs,
                               replicas={"edge": 2, "cloud": 1})
    for tier, eng in list(engines.items()) + [
            ("edge", tr.engine) for tr in pools["edge"].transports]:
        assert eng.cfg == cfgs[tier]
        assert {x.dtype for x in jax.tree.leaves(eng.params)} == {
            jnp.dtype(jnp.bfloat16)}
    assert engines["cloud"].cfg.num_layers == 1


def test_builders_default_to_reduced_float32_configs():
    engines = build_cluster_engines(two_tier_topology(), SV)
    for tier, eng in engines.items():
        assert eng.cfg == reduced_config(eng.cfg.name).replace(
            dtype="float32")
    with pytest.raises(ValueError, match="no model config"):
        build_cluster_engines(two_tier_topology(), SV,
                              configs={"edge": _bf16_configs()["edge"]})


def test_engine_device_pins_params_cache_and_keys():
    dev = jax.devices()[-1]
    pools = build_engine_pools(two_tier_topology(), SV,
                               replicas={"edge": 1, "cloud": 1},
                               devices=[dev])
    eng = pools["edge"].transports[0].engine
    assert eng.device == dev

    def pinned(tree):
        return all(x.devices() == {dev} and x.committed
                   for x in jax.tree.leaves(tree))

    assert pinned((eng.params, eng.cache, eng._keys))
    # a restored replica (the fault path) keeps its state on its device
    eng.restore(eng.snapshot())
    assert pinned((eng.cache, eng._keys))


def test_engine_pools_refuse_more_replicas_than_devices():
    with pytest.raises(ValueError, match="one replica per device"):
        build_engine_pools(two_tier_topology(), SV,
                           replicas={"edge": 2, "cloud": 1},
                           devices=jax.devices()[:1])


def test_compile_cache_follows_env(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_fixed_repo_dir(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    root = compile_cache.REPO_CACHE_DIR.parent
    assert path == str(root / ".jax_cache")
    assert (root / "chip_smoke.py").exists()


def _result(rid, failed=False, reason=""):
    return ServedResult(rid=rid, tier="edge", routes={}, tokens=[5],
                        latency_s=0.1, wan_s=0.0, failed=failed,
                        fail_reason=reason)


def test_serve_exit_status_fails_on_a_lost_request():
    ok = [_result(0), _result(1, True, "shed")]
    assert serve.exit_status(ok, faults_requested=False) == 0
    lost = ok + [_result(2, True, "retries")]
    assert serve.exit_status(lost, faults_requested=False) == 1


def test_serve_exit_status_allows_failures_when_faults_were_asked_for():
    lost = [_result(0), _result(1, True, "retries")]
    assert serve.exit_status(lost, faults_requested=True) == 0


def test_process_transport_refuses_a_non_cpu_backend(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(TransportError, match="local transport"):
        ProcessTransport(ReplicaSpec(model="qwen3-0.6b", serving=SV))


def test_serve_process_transport_on_a_non_cpu_backend_fails(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(serve, "enable_compile_cache", lambda: "")
    monkeypatch.setattr(sys, "argv", ["serve", "--transport", "process",
                                      "--requests", "1"])
    with pytest.raises(TransportError, match="tpu backend"):
        serve.main()
