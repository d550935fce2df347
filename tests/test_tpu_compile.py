"""Every Pallas kernel on the main paths compiles for a TPU v5e.

Interpret mode hides what the chip's compiler refuses: block shapes off
the (8, 128) tiling, more VMEM than a kernel may use, vector ops Mosaic
cannot lower. These tests hand each kernel, at the widths the sweep and
the served tier run, to the TPU compiler for a described (not attached)
v5e. The record kernel and the mining kernels are compiled at the
benchmark's table sizes (SUITE_MITHRIL) and at the paper's
(PAPER_MITHRIL), ``hash_lookup`` at both prefetch-table sizes, and
``paged_decode`` at each ``serving_bench`` tier geometry. The last tests
compile the sweep's whole chunk runner with its dispatch steered to
the kernels, as it is on a TPU: once at the SUITE tables for its named
scopes, and at the benchmark cells' paper-size tables for the layout of
the prefetch table inside the scan's step and inside the mining
barrier's loops (read-only and write-back), and for what the read-only
runner keeps from before write-back existed: its carry, its slab
arguments and its scatters.

The topology is described inside a module fixture, never at import:
one process at a time may load the TPU library, so only the test
worker that runs this file loads it, and every worker collects the
same tests. Nothing runs; only the compiler is exercised.
"""

import dataclasses
import functools
import importlib
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs.mithril_paper import PAPER_MITHRIL, SUITE_MITHRIL
from repro.kernels import ops

TABLES = {"suite": (SUITE_MITHRIL, 16), "paper": (PAPER_MITHRIL, 128)}
# serving_bench tier geometries: (batch, q heads, pool slots, pages/req)
DECODE = {"quick": (3, 4, 13, 4), "mid": (4, 4, 18, 4), "full": (5, 4, 22, 4)}
PAGE = (8, 2, 32)        # page_size, kv heads, head_dim


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A v5e device sharding, with the persistent compile cache off:
    what is compiled for a described chip cannot be read back here."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(sharding, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernels(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


def _kernels_named(text: str) -> list:
    """The name of each Pallas kernel launch in compiled HLO text."""
    return re.findall(r'^\s*(?:ROOT )?%(\w+)(?:\.\d+)* = .*'
                      r'custom_call_target="tpu_custom_call"', text,
                      flags=re.M)


@pytest.mark.parametrize("tables", sorted(TABLES))
def test_record_kernel_compiles(one_chip, tables):
    from repro.core import init_state
    cfg, lanes = TABLES[tables]
    states = jax.eval_shape(
        lambda: jax.vmap(lambda _: init_state(cfg))(jnp.arange(lanes)))
    states = jax.tree.map(lambda a: _sds(one_chip, a.shape, a.dtype), states)
    compiled = jax.jit(
        lambda s, b, e: ops.mithril_record_fused(s, b, e, interpret=False),
        donate_argnums=0).lower(
            states, _sds(one_chip, (lanes,)),
            _sds(one_chip, (lanes,), jnp.bool_)).compile()
    assert _kernels(compiled) == 1


@pytest.mark.parametrize("kind", ["serial", "barrier"])
@pytest.mark.parametrize("tables", sorted(TABLES))
def test_mining_kernel_compiles(one_chip, tables, kind):
    """The row-block mining kernel compiles alone, and inside
    ``mine_batched``'s loop over the flagged lanes at the table's lane
    count (the sweep's mining barrier) as one ``mithril_mine`` launch."""
    cfg, lanes = TABLES[tables]
    pairwise = functools.partial(ops.mithril_pairwise, interpret=False)
    if kind == "serial":
        n, s = cfg.mine_rows, cfg.max_support
        lowered = jax.jit(lambda ts, cnt, v: pairwise(
            ts, cnt, v, cfg.lookahead, cfg.window)).lower(
                _sds(one_chip, (n, s)), _sds(one_chip, (n,)),
                _sds(one_chip, (n,), jnp.bool_))
    else:
        from repro.core import init_state, mine_batched
        states = jax.eval_shape(
            lambda: jax.vmap(lambda _: init_state(cfg))(jnp.arange(lanes)))
        states = jax.tree.map(lambda a: _sds(one_chip, a.shape, a.dtype),
                              states)
        lowered = jax.jit(lambda st, need: mine_batched(
            cfg, st, need, pairwise_fn=pairwise)).lower(
                states, _sds(one_chip, (lanes,), jnp.bool_))
    text = lowered.compile().as_text()
    assert _kernels_named(text) == ["mithril_mine"]


@pytest.mark.parametrize("tables", sorted(TABLES))
def test_hash_lookup_compiles(one_chip, tables):
    cfg, _ = TABLES[tables]
    nb, w, p = cfg.pf_buckets, cfg.pf_ways, cfg.prefetch_list
    compiled = jax.jit(lambda q, k, v: ops.prefetch_lookup(
        q, k, v, interpret=False)).lower(
            _sds(one_chip, (512,)), _sds(one_chip, (nb, w)),
            _sds(one_chip, (nb, w, p))).compile()
    assert _kernels(compiled) == 1


@pytest.mark.parametrize("scale", sorted(DECODE))
def test_paged_decode_compiles(one_chip, scale):
    b, hq, slots, n_pg = DECODE[scale]
    ps, hkv, hd = PAGE
    pool = _sds(one_chip, (slots, ps, hkv, hd), jnp.float32)
    compiled = jax.jit(lambda q, k, v, t, ln: ops.paged_decode(
        q, k, v, t, ln, interpret=False)).lower(
            _sds(one_chip, (b, hq, hd), jnp.float32), pool, pool,
            _sds(one_chip, (b, n_pg)), _sds(one_chip, (b,))).compile()
    assert _kernels(compiled) == 1


_HLO_TEXT: dict = {}


def _runner_hlo(one_chip, monkeypatch, cfg, lanes=16, chunk=256) -> str:
    """Compiled HLO text of the sweep's chunk runner for ``cfg`` on the
    described chip, with the backend dispatch steered to the kernels;
    compiled once per configuration and shape in this module."""
    key = (cfg, lanes, chunk)
    if key not in _HLO_TEXT:
        _HLO_TEXT[key] = _compile_runner(one_chip, monkeypatch, cfg, lanes,
                                         chunk)
    return _HLO_TEXT[key]


def _compile_runner(one_chip, monkeypatch, cfg, lanes, chunk) -> str:
    from repro.kernels import backend
    sweep_mod = importlib.import_module("repro.cache.sweep")

    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    sweep_mod.reset_runners()
    try:
        init_batched, run_chunk, _ = sweep_mod._runner(cfg, 1, 1)
        carry = jax.tree.map(lambda a: _sds(one_chip, a.shape, a.dtype),
                             jax.eval_shape(lambda: init_batched(lanes)))
        slabs = [_sds(one_chip, (chunk, lanes)),
                 _sds(one_chip, (chunk, lanes), jnp.bool_)]
        if cfg.write_back:
            slabs.append(_sds(one_chip, (chunk, lanes), jnp.bool_))
        return run_chunk.lower(carry, *slabs).compile().as_text()
    finally:
        sweep_mod.reset_runners()


def _computations(text: str) -> dict:
    """Each computation of compiled HLO text -> its instruction lines."""
    comps, name = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY\s+)?%([\w.\-]+)\s*\(.*\{\s*$", line)
        if name is None and head:
            name, comps[head[1]] = head[1], []
        elif name is not None and line.strip() == "}":
            name = None
        elif name is not None:
            comps[name].append(line.strip())
    return comps


def _step_body(text: str) -> list:
    """The instructions of the scan's while body: the one computation
    that calls the barrier's ``lax.cond`` and holds MITHRIL's lookup."""
    def has(lines, pattern):
        return any(re.search(pattern, ln) for ln in lines)

    bodies = [lines for lines in _computations(text).values()
              if has(lines, r' conditional\(.*op_name="[^"]*/barrier/cond"')
              and has(lines, r'op_name="[^"]*/prefetch/[^"]*gather"')]
    assert len(bodies) == 1, len(bodies)
    return bodies[0]


def test_sweep_runner_compiles_with_kernels(one_chip, monkeypatch):
    """The chunk runner the sweep builds on a TPU (record kernel in the
    request step, the mining kernel at the barrier, AMP beside
    MITHRIL) compiles, and every part of its step keeps its named scope
    in the op_names XLA gives the loop body's operations."""
    from repro.cache import SimConfig

    cfg = SimConfig(capacity=512, use_mithril=True, use_amp=True,
                    mithril=SUITE_MITHRIL)
    text = _runner_hlo(one_chip, monkeypatch, cfg)
    assert text.count("tpu_custom_call") >= 2
    body = {c for name in re.findall(r'op_name="([^"]*)"', text)
            if "/while/body/" in name for c in name.split("/")}
    for scope in ("access", "record", "barrier", "prefetch", "amp"):
        assert scope in body, scope


@pytest.mark.parametrize("amp,write_back",
                         [(False, False), (True, False), (False, True)],
                         ids=["mithril", "mithril_amp", "mithril_wb"])
def test_sweep_step_keeps_prefetch_table_layout(one_chip, monkeypatch, amp,
                                                write_back):
    """At the benchmark cells' tables (the paper's R, S, Delta, P and
    table sizes, 1,250 mining rows, a fully associative 512-block cache,
    16 lanes, chunks of 256), no instruction of the scan's step writes
    the lanes' prefetch table ``pf_vals`` with its P axis minor-most;
    nor in the write-back cache, whose dirty table sits in the same
    bucket rows the prefetch inserts write.

    That layout pads each (ways, P) pair to a whole tile, and a lookup
    that reads a slot row whole makes the compiler relayout the entire
    table into it on every step, 64 times its bytes. The mining
    branches' own computations are not checked: they run only on the
    steps that mine."""
    from repro.cache import SimConfig

    mith = dataclasses.replace(PAPER_MITHRIL, mine_rows=1250)
    cfg = SimConfig(capacity=512, ways=512, use_mithril=True, use_amp=amp,
                    mithril=mith, write_back=write_back)
    table = "s32[16,{},{},{}]".format(mith.pf_buckets, mith.pf_ways,
                                      mith.prefetch_list)
    p_minor = re.escape(table) + r"\{3,"
    body = _step_body(_runner_hlo(one_chip, monkeypatch, cfg))
    padded = [ln.split(", metadata=")[0][:160] for ln in body
              if re.search(p_minor, ln.split(", metadata=")[0])]
    assert not padded, padded


def _barrier_loop_bodies(text: str) -> dict:
    """The body computations of every ``while`` under the ``barrier``
    scope (the mining barrier's loops) -> their instruction lines."""
    comps = _computations(text)
    loops = [re.search(r" while\(.*body=%([\w.\-]+)", ln)
             for lines in comps.values() for ln in lines
             if re.search(r'op_name="[^"]*/barrier/', ln)]
    return {m[1]: comps[m[1]] for m in loops if m}


@pytest.mark.parametrize("amp,write_back",
                         [(False, False), (True, False), (False, True)],
                         ids=["mithril", "mithril_amp", "mithril_wb"])
def test_mining_barrier_never_relays_lane_tables(one_chip, monkeypatch, amp,
                                                 write_back):
    """At the benchmark cells' tables, no loop of the mining barrier
    copies a lanes-stacked prefetch table (``pf_key``, ``pf_age`` or
    ``pf_vals`` of all 16 lanes). A pair fold vmapped over the lanes
    axis relays all three into other tiles on every folded pair, ~3.8M
    estimated cycles each; the barrier folds one lane's own tables at a
    time instead, whose loop holds no such copy."""
    from repro.cache import SimConfig

    mith = dataclasses.replace(PAPER_MITHRIL, mine_rows=1250)
    cfg = SimConfig(capacity=512, ways=512, use_mithril=True, use_amp=amp,
                    mithril=mith, write_back=write_back)
    tables = "|".join(re.escape("s32[16,{},{}{}]".format(
        mith.pf_buckets, mith.pf_ways, tail))
        for tail in ("", f",{mith.prefetch_list}"))
    copy = re.compile(r"= (?:{})\{{[^}}]*\}} copy\(".format(tables))
    bodies = _barrier_loop_bodies(_runner_hlo(one_chip, monkeypatch, cfg))
    assert bodies                  # the lanes loop and each lane's fold
    relays = [ln.split(", metadata=")[0][:160]
              for lines in bodies.values() for ln in lines
              if copy.search(ln.split(", metadata=")[0])]
    assert not relays, relays


# The read-only runner's cache and statistics leaves, as they were before
# write-back existed; (lanes, buckets, ways) tables and per-lane counters.
READ_ONLY_LEAVES = {
    **{f"['cache'].{f}": ((16, 1, 512), "int32")
       for f in ("key", "stamp", "pf_flag", "pf_sc", "pf_src", "freq",
                 "assoc")},
    "['cache'].clock": ((16,), "int32"),
    "['stats'].requests": ((16,), "int32"),
    "['stats'].hits": ((16,), "int32"),
    **{f"['stats'].{f}": ((16, 4), "int32")
       for f in ("pf_issued", "pf_used", "pf_evicted_unused")},
}


@pytest.mark.parametrize("amp,leaves,scatters",
                         [(False, 32, 25), (True, 38, 104)],
                         ids=["mithril", "mithril_amp"])
def test_read_only_runner_is_unchanged_by_write_back(one_chip, monkeypatch,
                                                     amp, leaves, scatters):
    """With ``write_back`` off, the benchmark cells' chunk runner keeps
    the carry, the slab arguments and the scatters it had before the
    write-back cache existed: no dirty table and no write-back counter in
    the carry, the slabs ``(int32 blocks, bool valid)`` and no third,
    no operation under the ``writeback`` scope, and as many scatter
    instructions in the v5e program (25 without AMP, 104 with)."""
    from repro.cache import SimConfig

    sweep_mod = importlib.import_module("repro.cache.sweep")
    mith = dataclasses.replace(PAPER_MITHRIL, mine_rows=1250)
    cfg = SimConfig(capacity=512, ways=512, use_mithril=True, use_amp=amp,
                    mithril=mith)
    sweep_mod.reset_runners()
    try:
        init_batched, run_chunk, _ = sweep_mod._runner(cfg, 1, 1)
        carry = jax.eval_shape(lambda: init_batched(16))
        flat = {jax.tree_util.keystr(p): (tuple(a.shape), str(a.dtype))
                for p, a in jax.tree_util.tree_flatten_with_path(carry)[0]}
        assert len(flat) == leaves
        assert {k: v for k, v in flat.items()
                if k.startswith(("['cache']", "['stats']"))} \
            == READ_ONLY_LEAVES
        blocks = jax.ShapeDtypeStruct((256, 16), jnp.int32)
        valid = jax.ShapeDtypeStruct((256, 16), jnp.bool_)
        jax.eval_shape(run_chunk, carry, blocks, valid)
        with pytest.raises(TypeError):
            jax.eval_shape(run_chunk, carry, blocks, valid, valid)
    finally:
        sweep_mod.reset_runners()
    text = _runner_hlo(one_chip, monkeypatch, cfg)
    assert "writeback" not in text
    assert len(re.findall(r" scatter\(", text)) == scatters
