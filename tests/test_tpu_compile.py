"""Compile guards: the served path's Pallas kernels lower for a TPU v5e.

Interpret mode (every other kernel test) cannot see what the TPU compiler
refuses — block shapes off the (8, 128) tiling, ops Mosaic does not
implement, more VMEM than a kernel may scope.  These tests compile, for a
described ``v5e:2x2`` topology with no chip attached, the candidate join
kernel and the strip gate at the real width and window (d = 768, block
64, 2^20 window rows) and the single-device multi-tenant scan step around
them, and check that each kernel is in the compiled program as a
``tpu_custom_call``.  Nothing runs, so nothing here speaks to results or
times.

The scan step is also read for what the compiler built around the
kernels: no instruction of the step may copy, pad or flatten the
kernel's level-1 candidate slabs on their way to the merge.

The topology is described inside a fixture (never at import): only one
process may hold the TPU library, and under pytest-xdist every worker
imports this file.
"""

import math
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.engine import EngineConfig
from repro.kernels.sssj_join import (
    CANDIDATE_KERNEL,
    GATE_KERNEL,
    init_strip_summary,
    sssj_join_candidates,
    strip_gate,
    tpu_kernels,
)
from repro.runtime import TenantTable
from repro.runtime.runtime import SingleDeviceFacade, make_tenant_batch_step

D, B, W = 768, 64, 1 << 20
TILE_K = B * B
CHUNK = 128


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A described-chip compile is written to the persistent cache but can
    never be read back without the chip; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree,
    )


def _summary(sharding):
    return _shapes(
        jax.eval_shape(
            lambda: init_strip_summary(W, D, block_w=B, chunk_d=CHUNK)
        ),
        sharding,
    )


def test_candidate_kernel_compiles_at_full_window(one_chip, no_persistent_cache):
    f32, i32 = jnp.float32, jnp.int32

    def S(shape, dtype=f32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def join(q, w, tq, tw, uq, uw, sq, sw, th, lm, summary):
        return sssj_join_candidates(
            q, w, tq, tw, uq, uw, theta=0.85, lam=3e-7, tile_k=TILE_K,
            block_q=B, block_w=B, chunk_d=CHUNK, impl="pallas",
            interpret=False, sq=sq, sw=sw, theta_q=th, lam_q=lm,
            summary=summary,
        )

    compiled = jax.jit(join).lower(
        S((B, D)), S((W, D)), S((B,)), S((W,)), S((B,), i32), S((W,), i32),
        S((B,), i32), S((W,), i32), S((B,)), S((B,)), _summary(one_chip),
    ).compile()
    assert CANDIDATE_KERNEL in tpu_kernels(compiled.as_text())


def test_strip_gate_compiles_over_full_window(one_chip, no_persistent_cache):
    def gate(qp, summary, tq_lo, tq_hi):
        return strip_gate(
            qp, summary, block_q=B, chunk_d=CHUNK, tq_lo=tq_lo, tq_hi=tq_hi,
            th_min=0.85, lam_min=3e-7, impl="pallas", interpret=False,
        )

    scalar = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    compiled = jax.jit(gate).lower(
        jax.ShapeDtypeStruct((B, D), jnp.float32, sharding=one_chip),
        _summary(one_chip), scalar, scalar,
    ).compile()
    assert GATE_KERNEL in tpu_kernels(compiled.as_text())


@pytest.fixture(scope="module")
def scan_step_hlo(one_chip, no_persistent_cache):
    """Compiled HLO text of the multi-tenant scan step: 8 tenants, a span of
    4 micro-batches, the Pallas join and gate at the full window."""
    thetas = np.linspace(0.85, 0.95, 8)
    lam = float(np.log(1 / 0.85) / (W // 2))
    table = TenantTable(thetas, [lam] * 8)
    cfg = EngineConfig(
        theta=0.85, lam=lam, capacity=W, d=D, micro_batch=B, tile_k=TILE_K,
        block_q=B, block_w=B, join_impl="pallas", interpret=False,
    )
    facade = SingleDeviceFacade()
    state = _shapes(jax.eval_shape(lambda: facade.init_state(cfg, table)),
                    one_chip)
    telem = _shapes(jax.eval_shape(lambda: facade.init_telemetry(cfg)),
                    one_chip)
    span = 4

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    step = make_tenant_batch_step(cfg, table)
    return step.lower(
        state, telem, S((span, B, D), jnp.float32), S((span, B), jnp.float32),
        S((span, B), jnp.int32), S((span, B), jnp.int32), S((span,), jnp.int32),
    ).compile().as_text()


def test_multi_tenant_scan_step_compiles(scan_step_hlo):
    kernels = tpu_kernels(scan_step_hlo)
    assert {CANDIDATE_KERNEL, GATE_KERNEL} <= kernels, kernels


_COMPUTATION = re.compile(r"^(?:ENTRY )?%(\S+) .*\{$")
_ARRAY_INSTR = re.compile(
    r"^\s+(?:ROOT )?%(\S+) = \w+\[([\d,]*)\]\S* ([\w-]+)\((.*)$"
)
_WHILE_BODY = re.compile(r"\bwhile\(.*\bbody=%([\w.-]+)")
_KERNEL_OUT = re.compile(rf"%{CANDIDATE_KERNEL}(?:\.\d+)?\)")


def _top_level_arrays(hlo_text: str):
    """``(name, dims, opcode, rest of line)`` of every array-valued
    instruction in the entry computation and in the while bodies (the
    scan over micro-batches and the loops inside it) — fusions' own
    internals are left out, since they live in registers and VMEM."""
    comps, entry, name = {}, None, None
    for line in hlo_text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            name = head.group(1)
            comps[name] = []
            if line.startswith("ENTRY "):
                entry = name
        elif name is not None:
            comps[name].append(line)
    bodies = {m.group(1) for m in _WHILE_BODY.finditer(hlo_text)}
    for comp in {entry} | bodies:
        for line in comps[comp]:
            m = _ARRAY_INSTR.match(line)
            if m:
                dims = tuple(int(x) for x in m.group(2).split(",") if x)
                yield m.group(1), dims, m.group(3), m.group(4)


def test_scan_step_merges_slabs_in_place(scan_step_hlo):
    """Every full-window-sized result of the step is the ring itself (a
    parameter or its scatter update), the candidate kernel's slab output,
    or a bitcast or tuple element of those: the level-2 merge gathers its
    ``max_pairs`` entries from the slabs where the kernel wrote them, with
    no relayout copy, no concatenation with the self-join's segment and
    no flattening."""
    big = (W // B) * TILE_K
    ring = (W, D)
    slabs, offending = 0, []
    for name, dims, opcode, rest in _top_level_arrays(scan_step_hlo):
        if math.prod(dims) < big:
            continue
        if opcode == "get-tuple-element" and _KERNEL_OUT.match(rest):
            slabs += 1
        elif opcode in ("parameter", "get-tuple-element", "bitcast"):
            pass
        elif dims == ring and opcode in ("fusion", "scatter",
                                         "dynamic-update-slice"):
            pass
        else:
            offending.append(f"%{name} = {opcode} {list(dims)}")
    assert slabs == 3, f"expected the kernel's 3 candidate slabs, saw {slabs}"
    assert not offending, (
        "slab-sized instructions besides the ring and the kernel's slabs: "
        + "; ".join(offending)
    )
