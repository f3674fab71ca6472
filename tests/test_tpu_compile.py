"""Compile the main path's programs for a TPU v5e that is described, not
attached (on-chip-measurement guide §2), plus the chip entry point's
guards.

The TPU compiler is installed here, so the programs the ``tpu`` and
``tpu-sharded`` backends dispatch at RMAT-22 widths (V = 2^22 vertices,
C = 2^23 edges per chunk) compile for one v5e chip and for a 2x2 mesh in
this process: what the chip's compiler would refuse, or a program that
does not fit 16 GB of HBM, fails here at no chip time.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library, so a description at
collection time would make xdist workers collect different tests.
"""

import os
import shutil
import subprocess
import sys
import time

import pytest

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
V = 1 << 22          # RMAT-22 vertices
C = 1 << 23          # chip_smoke / bench chunk width
HBM = 16 * 10 ** 9   # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import compilation_cache as cc
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described device is written to the persistent
    # cache but cannot be read back without a chip: keep it out
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, sharding, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _fits(compiled, budget=HBM):
    ma = compiled.memory_analysis()
    used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert 0 < used < budget, used
    return ma


def test_segment_fold_batch_donated_compiles(one_chip):
    """The batched, donated segment fold (ops/elim.py) at V = 2^22,
    C = 2^23, at N = 16, the widest batch the chip's HBM held before
    the default became per-segment: donation must alias the carried
    table and both staging blocks into the outputs."""
    from sheep_tpu.ops import elim

    B = 16
    compiled = elim.fold_segments_batch_pos_donated.lower(
        _sds((V + 1,), one_chip), _sds((B, C), one_chip),
        _sds((B, C), one_chip), V, lift_levels=0, descent="auto",
        batch_rounds=2 * B).compile()
    ma = _fits(compiled)
    assert ma.alias_size_in_bytes >= (V + 1 + 2 * B * C) * 4


def test_hoisted_fold_compiles_at_the_batch_cell_shape(one_chip):
    """The tpu backend's full-width segment (ops/elim.py
    fold_segment_pos_hoisted) at the Graph500 scale-20 cell's shape,
    V = 2^20 and C = 2^22: the all-sentinel levels' conds compile, the
    20-table stack fits HBM, and the stats word carries the live-level
    count."""
    from sheep_tpu.ops import elim

    v, c = 1 << 20, 1 << 22
    compiled = elim.fold_segment_pos_hoisted.lower(
        _sds((v + 1,), one_chip), _sds((c,), one_chip), _sds((c,), one_chip),
        v, lift_levels=0, segment_rounds=2).compile()
    _fits(compiled)
    assert compiled.out_info[3].shape == (4,)


def test_warm_collapse_fold_compiles_at_the_batch_cell_shape(one_chip):
    """The tpu backend's warm segment (ops/elim.py
    fold_segment_pos_collapse) at the same shape, V = 2^20 and
    C = 2^22, with the cell's warm schedule (one round, 8 levels,
    stream descent): the rounds and the two-key sort of the duplicate
    collapse under its cond compile as one program, the threshold is a
    traced scalar, and the stats word carries the dropped count."""
    from sheep_tpu.ops import elim

    v, c = 1 << 20, 1 << 22
    compiled = elim.fold_segment_pos_collapse.lower(
        _sds((v + 1,), one_chip), _sds((c,), one_chip), _sds((c,), one_chip),
        _sds((), one_chip), v, lift_levels=8, segment_rounds=1,
        descent="stream").compile()
    _fits(compiled)
    assert compiled.out_info[3].shape == (4,)


@pytest.mark.parametrize("program", ["score_chunk", "orient_batch"])
def test_chunk_programs_compile(one_chip, program):
    """Score (ops/score.py) and the batch orient that stages the fold's
    [N, C] blocks, at the same widths."""
    from sheep_tpu.ops import elim, score

    if program == "score_chunk":
        lowered = score.score_chunk.lower(
            _sds((C, 2), one_chip), _sds((V + 1,), one_chip), V)
    else:
        lowered = elim.orient_chunks_batch_pos.lower(
            _sds((2, C, 2), one_chip), _sds((V + 1,), one_chip), V)
    _fits(lowered.compile())


def _compile_s(lowered):
    t0 = time.perf_counter()
    compiled = lowered.compile()
    return compiled, time.perf_counter() - t0


def test_sheepd_degree_program_compiles(one_chip):
    """sheepd's RMAT-18 degree program (V = 2^18, chunks of 2^20 edges).
    Scattering the flattened (2C,) endpoint ids compiled for 283 s on
    the chip (PR 21); one scatter per column compiles in about a
    second."""
    from sheep_tpu.ops import degrees

    v, c = 1 << 18, 1 << 20
    compiled, secs = _compile_s(degrees.degree_chunk.lower(
        _sds((v + 1,), one_chip), _sds((c, 2), one_chip), v))
    _fits(compiled)
    assert secs < 60, secs


def test_sharded_fold_compiles_on_4_chip_mesh(topo):
    """The sharded, donated fold (parallel/pipeline.py) on a 4-device
    mesh built from the described devices: per-device tables and
    staging blocks stay sharded, and the lockstep stats word is the
    only collective output."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from sheep_tpu.parallel.pipeline import ShardedPipeline

    mesh = Mesh(list(topo.devices), ("shards",))
    D, B, cs = 4, 2, 1 << 22
    pipe = ShardedPipeline(V, cs, mesh, dispatch_batch=B, inflight=2,
                           donate=True)
    blocks = NamedSharding(mesh, P("shards", None, None))
    compiled = pipe.fold_batch_step_donated.lower(
        _sds((D, V + 1), pipe.state_sharding), _sds((D, B, cs), blocks),
        _sds((D, B, cs), blocks)).compile()
    _fits(compiled)
    hlo = compiled.as_text()
    assert "all-reduce" in hlo


def test_sharded_adaptive_fold_compiles_on_4_chip_mesh(topo):
    """tpu-sharded's default fold (per-segment, adaptive): its
    full-width segment step at its default chunk (2^22 edges per
    device) on the described 2x2 mesh, with the pmax'd lockstep flags
    as the only collectives."""
    from jax.sharding import Mesh

    from sheep_tpu.parallel.pipeline import ShardedPipeline

    mesh = Mesh(list(topo.devices), ("shards",))
    D, cs = 4, 1 << 22
    pipe = ShardedPipeline(V, cs, mesh)
    st = pipe.state_sharding
    compiled = pipe._fold_full.lower(
        _sds((D, V + 1), st), _sds((D, cs), st), _sds((D, cs), st)).compile()
    _fits(compiled)
    assert "all-reduce" in compiled.as_text()


def test_bigv_degree_step_compiles_on_4_chip_mesh(topo):
    """tpu-bigv's routed degree step at its default chunk (2^20 edges per
    device) on the described 2x2 mesh: the flattened-ids form compiled
    for 219 s (PR 21), one scatter per column in a few seconds."""
    from jax.sharding import Mesh

    from sheep_tpu.parallel.bigv import BigVPipeline

    mesh = Mesh(list(topo.devices), ("shards",))
    cb = 1 << 20
    pipe = BigVPipeline(V, cb, mesh)
    compiled, secs = _compile_s(pipe.deg_step.lower(
        _sds((pipe.rows,), pipe.shard),
        _sds((4, cb, 2), pipe.batch_sharding)))
    _fits(compiled)
    assert secs < 60, secs


# ---------------------------------------------------------------------------
# the chip entry points' guards (no topology needed)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("env_dir", [True, False])
def test_compilation_cache_dir_rule(monkeypatch, tmp_path, env_dir):
    """JAX_COMPILATION_CACHE_DIR wins and nothing else is configured;
    without it the cache is <repo>/.jax_cache."""
    from sheep_tpu.utils import platform

    if env_dir:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    prev = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        got = platform.enable_compilation_cache()
        if env_dir:
            assert got == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir is None
        else:
            assert got == os.path.join(REPO, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


@pytest.mark.parametrize("alone", [False, True])
def test_chip_smoke_fails_without_a_tpu(tmp_path, alone):
    """On a CPU-only host chip_smoke.py exits non-zero with a FAIL line
    and prints no result — from the checkout, and from a directory
    holding chip_smoke.py and nothing else of the repo."""
    script = os.path.join(REPO, "chip_smoke.py")
    cwd = REPO
    if alone:
        shutil.copy(script, tmp_path / "chip_smoke.py")
        script, cwd = str(tmp_path / "chip_smoke.py"), str(tmp_path)
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "PYTHONPATH")}
    env["TPU_LOG_DIR"] = "disabled"
    r = subprocess.run([sys.executable, script], capture_output=True,
                       text=True, env=env, cwd=cwd, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "chip_smoke: FAIL" in r.stderr
