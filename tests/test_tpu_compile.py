"""Compiles for a described TPU v5e: the main path's Pallas kernels and
the dqn, rainbow and r2d2 population cycles at their cells' sizes.

Nothing runs here. The TPU compiler that ships with libtpu compiles for
a chip that is described, not attached, so a kernel Mosaic would refuse
(an unsupported primitive, a bad layout, too much VMEM) or a cycle that
would not fit the chip's 16 GB of HBM fails here instead of on the chip.
The interpreter accepts both kernels' bodies as written for it, which
is why ``tests/test_backend_dispatch.py`` cannot catch these faults.

The topology is described inside a module-scoped fixture, never while
the module is imported, so every test worker collects the same tests and
only the worker given this file loads libtpu.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

V5E_HBM_BYTES = 16 * 1024 ** 3


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:   # no TPU compiler on this host
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip cannot be read back from the
        # persistent cache: keep it off around these compiles
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            cc.reset_cache()


def _shape(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("vmapped", [False, True])
@pytest.mark.parametrize("leaves", [16_384, 1 << 20])
def test_segment_tree_compiles_for_v5e(one_chip, leaves, vmapped):
    from repro.kernels import ops

    def sample(tree, targets):
        return ops.segment_tree_sample(tree, targets, backend="mosaic")

    lead = (1,) if vmapped else ()
    _compile(jax.vmap(sample) if vmapped else sample,
             _shape(one_chip, lead + (2 * leaves,)),
             _shape(one_chip, lead + (32,)))


@pytest.mark.parametrize("vmapped", [False, True])
def test_categorical_projection_compiles_for_v5e(one_chip, vmapped):
    from repro.kernels import ops

    def project(p, r, d):
        return ops.categorical_projection(p, r, d, -10.0, 10.0, 0.99 ** 3,
                                          backend="mosaic")

    lead = (1,) if vmapped else ()
    _compile(jax.vmap(project) if vmapped else project,
             _shape(one_chip, lead + (32, 51)),
             _shape(one_chip, lead + (32,)), _shape(one_chip, lead + (32,)))


HLO_INSTR = re.compile(r"%([\w.-]+) = (\w+\[[\d,]*\])(\{[^}]*\})? "
                       r"([\w-]+)\(%?([\w.-]*)")
REPLAY_FRAMES = re.compile(r"replay\[\\'(next_)?obs\\'\]")
SEQUENCE_FRAMES = re.compile(r"replay\[\\'frames\\'\]")


def frame_relayouts(hlo_text, stores=REPLAY_FRAMES):
    """The replay frame stores' shapes among ENTRY's parameters, and the
    ``copy`` instructions of ENTRY that give such a store another layout
    than their operand has."""
    entry = hlo_text[hlo_text.index("\nENTRY"):]
    layouts, frames, copies = {}, set(), []
    for line in entry.splitlines():
        m = HLO_INSTR.search(line)
        if m is None:
            continue
        name, shape, layout, opcode, operand = m.groups()
        layouts[name] = layout
        if opcode == "parameter" and stores.search(line):
            frames.add(shape)
        elif opcode == "copy":
            copies.append((name, shape, layout, operand))
    return frames, [c for c in copies
                    if c[1] in frames and c[2] != layouts.get(c[3])]


def _check_population_cycle(one_chip, monkeypatch, variant):
    """The population cycle at 84x84x4 with replay 75,000 compiles for
    one v5e and fits its HBM; every phase's named scope survives the TPU
    compiler's fusion into its op_name metadata; and the replay's frame
    stores keep their entry layout: no copy relayouts them around the
    draws or the flush (six 2.1 GB relayouts and 6.8 GB of temporaries
    when a stack was stored as ``u8[capacity, 84, 84, 4]``)."""
    import dataclasses

    from test_concurrent import scopes_found

    from repro.api import ExperimentSpec, build_trainer
    from repro.configs.dqn_nature import get_variant
    from repro.core.concurrent import (CYCLE_SCOPES, PER_TREE_SCOPE,
                                       RECURRENT_SCOPES)

    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "mosaic")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "examples", "specs",
                           "dqn_nature84.json")) as f:
        spec = ExperimentSpec.from_json(f.read())
    spec = dataclasses.replace(
        spec, mode="population", variant=get_variant(variant),
        algo=dataclasses.replace(spec.algo, replay_capacity=75_000))
    trainer = build_trainer(spec)
    carry = jax.tree.map(lambda s: _shape(one_chip, s.shape, s.dtype),
                         trainer.init_template())
    compiled = trainer.cycle.lower(carry).compile()
    text = compiled.as_text()
    want = set(CYCLE_SCOPES) - set(RECURRENT_SCOPES)
    if variant == "dqn":
        want.discard(PER_TREE_SCOPE)
    else:
        assert "tpu_custom_call" in text
    assert scopes_found(text) == want
    frames, relayouts = frame_relayouts(text)
    assert frames == {"u32[1,75000,7168]"}
    assert not relayouts, relayouts
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 3e9, mem.temp_size_in_bytes
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < V5E_HBM_BYTES, total


def test_rainbow_population_cycle_compiles_for_v5e(one_chip, monkeypatch):
    """The rainbow cycle, with both Mosaic kernels inside it."""
    _check_population_cycle(one_chip, monkeypatch, "rainbow")


def test_dqn_population_cycle_compiles_for_v5e(one_chip, monkeypatch):
    _check_population_cycle(one_chip, monkeypatch, "dqn")


def test_r2d2_population_cycle_compiles_for_v5e(one_chip, monkeypatch):
    """The r2d2-nature.w256 cell's cycle (W=256, 16,384 sequence leaves,
    84x84 frames), with the segment_tree kernel at its leaf count, fits
    one v5e; every scope of the cycle, the recurrent ones included,
    survives into its op_name metadata; and the sequence replay's frame
    store, one lane-aligned row per step, keeps its entry layout."""
    import json

    from test_concurrent import scopes_found

    from repro.api import ExperimentSpec, build_trainer
    from repro.core.concurrent import CYCLE_SCOPES

    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "mosaic")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "bench", "configs",
                           "r2d2-nature.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(root, "bench", "traffic", "w256.json")) as f:
        traffic = json.load(f)
    spec = ExperimentSpec.from_dict(dict(
        cfg["spec"], env=traffic["env"], envs=traffic["envs"], seeds=1))
    trainer = build_trainer(spec)
    carry = jax.tree.map(lambda s: _shape(one_chip, s.shape, s.dtype),
                         trainer.init_template())
    compiled = trainer.cycle.lower(carry).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert scopes_found(text) == set(CYCLE_SCOPES)
    frames, relayouts = frame_relayouts(text, SEQUENCE_FRAMES)
    assert frames == {"u32[1,655360,1792]"}
    assert not relayouts, relayouts
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 3e9, mem.temp_size_in_bytes
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < V5E_HBM_BYTES, total
