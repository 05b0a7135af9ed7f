"""Launch-layer tests: logical-spec resolution + HLO cost parser."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.launch import hlo_cost
from repro.launch import mesh as mesh_lib


# ---------------------------------------------------------------------------
# Spec resolution
# ---------------------------------------------------------------------------

def test_resolve_spec_single_pod():
    assert mesh_lib.resolve_spec(P("fsdp", "model"), False) == \
        P("data", "model")
    assert mesh_lib.resolve_spec(P("batch", None), False) == P("data", None)
    assert mesh_lib.resolve_spec(P(None, "batch", "seq2"), False) == \
        P(None, "data", ("data", "model"))


def test_resolve_spec_multi_pod():
    assert mesh_lib.resolve_spec(P("batch", None), True) == \
        P(("pod", "data"), None)
    assert mesh_lib.resolve_spec(P("fsdp", "model"), True) == \
        P("data", "model")


def test_resolve_tree_preserves_structure():
    tree = {"a": P("batch"), "b": {"c": P(None, "model")}}
    out = mesh_lib.resolve_tree(tree, False)
    assert out["a"] == P("data")
    assert out["b"]["c"] == P(None, "model")


def test_batch_axes():
    assert mesh_lib.batch_axes(False) == ("data",)
    assert mesh_lib.batch_axes(True) == ("pod", "data")


# ---------------------------------------------------------------------------
# HLO cost parser: trip-count weighting on a known program
# ---------------------------------------------------------------------------

def test_hlo_cost_counts_scan_trip_counts():
    """A scan of N matmuls must report ~N x the flops of one matmul."""
    d, n_iters = 64, 10

    def f(x, ws):
        def body(x, w):
            return jnp.tanh(x @ w), None
        out, _ = jax.lax.scan(body, x, ws)
        return out

    x = jnp.ones((8, d), jnp.float32)
    ws = jnp.ones((n_iters, d, d), jnp.float32)
    compiled = jax.jit(f).lower(x, ws).compile()
    rec = hlo_cost.analyze(compiled.as_text())
    one_matmul = 2 * 8 * d * d
    assert rec["flops_per_device"] == pytest.approx(n_iters * one_matmul,
                                                    rel=0.05)


def test_hlo_cost_no_loops():
    def f(a, b):
        return a @ b

    a = jnp.ones((32, 16), jnp.float32)
    b = jnp.ones((16, 8), jnp.float32)
    compiled = jax.jit(f).lower(a, b).compile()
    rec = hlo_cost.analyze(compiled.as_text())
    assert rec["flops_per_device"] == pytest.approx(2 * 32 * 16 * 8, rel=0.01)
    # bytes: at least inputs + outputs once
    assert rec["bytes_per_device"] >= (32 * 16 + 16 * 8 + 32 * 8) * 4


def test_hlo_cost_nested_scans_multiply():
    d, outer, inner = 32, 4, 5

    def f(x, ws):
        def outer_body(x, wgrp):
            def inner_body(x, w):
                return x @ w, None
            out, _ = jax.lax.scan(inner_body, x, wgrp)
            return out, None
        out, _ = jax.lax.scan(outer_body, x, ws)
        return out

    x = jnp.ones((4, d), jnp.float32)
    ws = jnp.ones((outer, inner, d, d), jnp.float32)
    compiled = jax.jit(f).lower(x, ws).compile()
    rec = hlo_cost.analyze(compiled.as_text())
    assert rec["flops_per_device"] == pytest.approx(
        outer * inner * 2 * 4 * d * d, rel=0.05)


def test_shape_bytes_parser():
    assert hlo_cost._shape_bytes("bf16[2,3]{1,0}") == 12
    assert hlo_cost._shape_bytes("(f32[4], s8[8])") == 24
    assert hlo_cost._shape_bytes("pred[]") == 1      # scalar: one element


# ---------------------------------------------------------------------------
# Device peaks (one table, keyed by device_kind)
# ---------------------------------------------------------------------------

def test_device_peaks_v5e_published_values():
    from repro.launch import roofline
    peaks = roofline.device_peaks("TPU v5 lite")
    assert peaks["bf16_flops"] == 197e12
    assert peaks["hbm_bytes_per_s"] == 819e9


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5p", ""])
def test_device_peaks_unknown_kind_raises(kind):
    """A device without published peaks is an error, never a default."""
    from repro.launch import roofline
    with pytest.raises(KeyError, match="no published peaks"):
        roofline.device_peaks(kind)


def test_roofline_terms_use_the_named_device():
    from repro.launch import roofline
    rec = {"hlo_cost": {"flops_per_device": 197e12, "bytes_per_device":
                        819e9, "collective_bytes_per_device": 0.0,
                        "collective_bytes_by_type": {}},
           "num_devices": 1, "arch": "qwen3-1.7b",
           "shape": next(iter(roofline.SHAPES)), "mesh": "16x16",
           "kind": "train"}
    row = roofline.analyze_record(rec)
    assert row["compute_s"] == pytest.approx(1.0)
    assert row["memory_s"] == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# Persistent compilation cache location
# ---------------------------------------------------------------------------

@pytest.fixture
def restore_cache_config():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def test_compile_cache_defaults_to_fixed_checkout_dir(monkeypatch,
                                                      restore_cache_config):
    from repro.launch import compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == compile_cache.CHECKOUT_CACHE_DIR
    assert path.endswith(".jax_cache")
    root = os.path.dirname(path)
    assert os.path.isdir(os.path.join(root, "src", "repro"))
    assert jax.config.jax_compilation_cache_dir == path
    assert jax.config.jax_persistent_cache_min_compile_time_secs \
        == compile_cache.MIN_COMPILE_SECONDS


def test_compile_cache_leaves_env_dir_to_jax(monkeypatch, tmp_path,
                                             restore_cache_config):
    """With JAX_COMPILATION_CACHE_DIR set, no other directory is set."""
    from repro.launch import compile_cache
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir is None
