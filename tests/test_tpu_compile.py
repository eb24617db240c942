"""Compile the enumeration hot path for a described TPU v5e, without a chip.

The TPU compiler is installed with JAX, and it compiles for a chip that is
described rather than attached (``jax.experimental.topologies``). That
refuses what interpret mode accepts: block shapes off the (8, 128) tiling,
more VMEM than a kernel may use, a program that does not fit the device. The
shapes are the one-chip smoke's real size: ``powerlaw_graph(2**16, 8.0)``
pads adjacency rows to 3712 lanes, batches are 1024 rows of 3 columns and an
extend intersects 2 or 3 slabs. The unfused extend is also compiled at the
benchmark graph ``go16``'s widths (3200 lanes, one call 32768 rows wide).

The topology is described inside a module fixture, never at import: only one
process may hold the TPU library, and every test worker imports this file.
"""
from __future__ import annotations

import math
import os
import re

import pytest

D_PAD, BATCH, K, ROWS = 3712, 1024, 3, 1 << 16
GO16_D_PAD, GO16_WIDTH = 3200, BATCH * 32  # mean degree 9.87: 32 lanes a row


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep these compiles out of it.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def spec(topo):
    """``spec(shape)`` -> an int32 shape on one described chip."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)


def _slab_args(spec, e):
    return (spec((ROWS, D_PAD)), spec((ROWS, D_PAD)), spec((2, BATCH, e)),
            spec((BATCH, e)), spec((BATCH, e)), spec((BATCH, K)))


@pytest.mark.parametrize("e", [2, 3])
def test_fused_extend_kernel_compiles(spec, e):
    from repro.kernels.intersect.intersect import fused_extend_kernel

    compiled = fused_extend_kernel.lower(
        *_slab_args(spec, e), lt=(0,), gt=(1,)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_verify_kernel_compiles(spec):
    from repro.kernels.intersect.intersect import fused_verify_kernel

    compiled = fused_verify_kernel.lower(*_slab_args(spec, 2), vpos=2).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_lex_bounds_kernel_compiles(spec):
    from repro.kernels.intersect.intersect import lex_bounds_kernel

    compiled = lex_bounds_kernel.lower(spec((1 << 20, 2)), spec((BATCH, 2))).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_multiway_membership_kernel_compiles(spec):
    from repro.kernels.intersect.intersect import multiway_membership_kernel

    compiled = multiway_membership_kernel.lower(
        spec((BATCH, D_PAD)), spec((BATCH, 2, D_PAD))).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_extend_step_compiles(spec, monkeypatch):
    """The fused step as the engine jits it: kernel plus expand/compact.
    Kernel dispatch asks the default backend, which is the CPU here; the
    test steers it to the TPU branch the chip would take."""
    from repro.core import operators as ops_mod
    from repro.kernels.intersect import ops as ik

    monkeypatch.setattr(ik, "_on_tpu", lambda: True)

    compiled = ops_mod.fused_extend_batch.lower(
        *_slab_args(spec, 2), spec(()), lt=(0,), gt=(), out_cap=BATCH * D_PAD,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_unfused_extend_step_compiles(spec, monkeypatch):
    """The unfused extend step (``fused=False``, the default) at the same
    widths: its membership is the native kernel, and its working set fits
    one chip's 16 GB."""
    from repro.core import operators as ops_mod
    from repro.kernels.intersect import ops as ik

    monkeypatch.setattr(ik, "_on_tpu", lambda: True)

    compiled = ops_mod.extend_batch.lower(
        spec((ROWS, D_PAD)), spec((BATCH, K)), spec(()), ext=(0, 1, 2),
        lt=(0,), gt=(), out_cap=BATCH * D_PAD,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < 16 << 30, used


def _index_counts(hlo: str):
    """``(op, indices)`` of every gather and scatter in compiled HLO text:
    the elements of its index operand, less the index vector's axis."""
    shapes = dict(re.findall(r"%(\S+) = \w+\[([\d,]*)\]", hlo))
    found = []
    for op, args, vdim in re.findall(
            r"\b(gather|scatter)\(%\S+, %([^,)\s]+).*?index_vector_dim=(\d+)",
            hlo):
        dims = [int(x) for x in shapes[args].split(",") if x]
        found.append((op, math.prod(dims) // (dims[int(vdim)]
                                              if int(vdim) < len(dims) else 1)))
    return found


@pytest.mark.parametrize("k, ext", [(2, (0, 1)), (3, (0, 1, 2))])
def test_narrow_extend_gathers_no_bdpad_indices(spec, monkeypatch, k, ext):
    """Both of ``go16``'s unfused extends, as the engine calls them: every
    gather and scatter runs over the batch's rows or the call's output
    width, none over ``B·d_pad`` (the expand-and-compact this replaced ran
    a 3,276,800-index scatter and gather each step)."""
    from repro.core import operators as ops_mod
    from repro.kernels.intersect import ops as ik

    monkeypatch.setattr(ik, "_on_tpu", lambda: True)

    hlo = ops_mod.extend_batch.lower(
        spec((ROWS, GO16_D_PAD)), spec((BATCH, k)), spec(()), ext=ext,
        lt=(0,), gt=(), out_cap=GO16_WIDTH, j0=spec(()),
    ).compile().as_text()
    counts = _index_counts(hlo)
    assert {op for op, _ in counts} == {"gather", "scatter"}
    assert max(c for _, c in counts) <= GO16_WIDTH, counts


def _small_kernel_calls(spec):
    """Each Pallas kernel of the hot path with small shapes, by its name."""
    from repro.kernels.intersect import intersect as k

    b, d = 64, 256
    slabs = (spec((1 << 10, d)), spec((1 << 10, d)), spec((2, b, 2)),
             spec((b, 2)), spec((b, 2)), spec((b, K)))
    return {
        "multiway_membership_kernel": lambda: k.multiway_membership_kernel.lower(
            spec((b, d)), spec((b, 2, d))),
        "fused_extend_kernel": lambda: k.fused_extend_kernel.lower(*slabs, lt=(0,)),
        "fused_verify_kernel": lambda: k.fused_verify_kernel.lower(*slabs, vpos=2),
        "lex_bounds_kernel": lambda: k.lex_bounds_kernel.lower(
            spec((1 << 10, 2)), spec((b, 2))),
    }


@pytest.mark.parametrize("name", ["multiway_membership_kernel",
                                  "fused_extend_kernel", "fused_verify_kernel",
                                  "lex_bounds_kernel"])
def test_kernel_keeps_its_name(spec, name):
    """The kernel's own name is pinned, and the chip's custom call, whose
    name a device trace shows, carries it."""
    lowered = _small_kernel_calls(spec)[name]()
    assert f'kernel_name = "{name}"' in lowered.as_text()
    hlo = lowered.compile().as_text()
    assert re.search(rf"%{name}(\.\d+)? = .* custom-call\(.*"
                     r'custom_call_target="tpu_custom_call"', hlo), name
