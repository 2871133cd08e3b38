"""The main path's Pallas kernels compiled for a described TPU v5e chip at
Qwen1.5-0.5B widths (16 KV heads of 64, d 1024, d_ff 2816), and the
selective scan at the Mamba-2.8B prune cell's (8 x 2048 tokens, d_inner
5120, d_state 16).

Interpret mode and the CPU tests never run Mosaic, which refuses blocks
that break the TPU tiling rules; these compiles do (``interpret=False``,
calling the kernels directly because ``kernels.ops`` sees the CPU).
Nothing runs, so this says nothing about results or speed.  The topology
is described inside a fixture, never at import: only the worker that is
given this file loads the TPU compiler.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.nm_spmm import nm_spmm, nm_spmm_decode
from repro.kernels.paged_attn import paged_attn
from repro.kernels.selective_scan import selective_scan
from repro.utils.hlo import tpu_kernel_names

KV, HD, PAGE, P_MAX, BATCH, PAGES = 16, 64, 16, 64, 8, 513


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("pages", ["bf16", "int8"])
def test_paged_attn_compiles(one_chip, pages):
    page = (PAGES, PAGE, KV, HD)
    shapes = [((BATCH, KV, 1, HD), jnp.bfloat16),
              (page, jnp.dtype(pages if pages == "int8" else jnp.bfloat16)),
              (page, jnp.dtype(pages if pages == "int8" else jnp.bfloat16)),
              ((BATCH, P_MAX), jnp.int32), ((BATCH,), jnp.int32)]
    if pages == "int8":
        shapes += [((PAGES, PAGE, KV), jnp.float32)] * 2

        def fn(q, kp, vp, bt, ln, ks, vs):
            return paged_attn(q, kp, vp, bt, ln, k_scale=ks, v_scale=vs)
    else:
        fn = paged_attn
    assert tpu_kernel_names(_compile(one_chip, fn, *shapes)) == \
        ["paged_attn"]


@pytest.mark.parametrize("k,n", [(1024, 2816), (2816, 1024)])
def test_nm_spmm_decode_compiles(one_chip, k, n):
    def fn(x, vals, idx, bias):
        return nm_spmm_decode(x, vals, idx, bias, activation="silu")

    hlo = _compile(one_chip, fn, ((8, k), jnp.bfloat16),
                   ((k // 2, n), jnp.bfloat16), ((k // 2, n), jnp.int8),
                   ((1, n), jnp.float32))
    assert tpu_kernel_names(hlo) == ["nm_spmm_decode"]


@pytest.mark.parametrize("k,n", [(1024, 2816), (2816, 1024)])
def test_nm_spmm_compiles(one_chip, k, n):
    hlo = _compile(one_chip, nm_spmm, ((256, k), jnp.bfloat16),
                   ((k // 2, n), jnp.bfloat16), ((k // 2, n), jnp.int8))
    assert tpu_kernel_names(hlo) == ["nm_spmm"]


@pytest.mark.parametrize("carry", [False, True])
def test_selective_scan_compiles(one_chip, carry):
    b, t, di, n = 8, 2048, 5120, 16
    shapes = [((b, t, di), jnp.float32)] * 2 + [((b, t, n), jnp.float32)] * 2
    shapes.append(((di, n), jnp.float32))
    if carry:
        shapes.append(((b, di, n), jnp.float32))
    hlo = _compile(one_chip, selective_scan, *shapes)
    assert tpu_kernel_names(hlo) == ["selective_scan"]
