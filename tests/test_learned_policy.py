"""Learned eviction scatter form vs a plain-NumPy frozen oracle.

The tentpole contract (ISSUE 8 / DESIGN.md §12): the learned
admission/eviction path is branchless scatter form — same shape as AMP
(``tests/test_amp_scatter.py``) — and its scoring is int32 fixed point
end to end, so a plain-NumPy re-implementation with Python control flow
reproduces the jitted path *bit for bit, per event* (float scoring
would not survive XLA:CPU's shape-dependent FMA contraction — the
integer form is what keeps the serial simulator and the vmapped sweep
agreeing on every eviction). The oracle here
re-implements scoring AND the full scored access/prefetch-insert
semantics (second chance included) in NumPy and compares every state
leaf after every event. ``enabled=False`` must stay a bit-exact no-op —
that is the mechanism freezing padded-tail lanes under the sweep vmap
(the learned configs also ride ``tests/test_sweep.py``'s
sweep-vs-simulate padded-suite pinning via ``benchmarks.common
.configs``).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.cache import base
from repro.cache.base import PF_MITHRIL, PF_NONE
from repro.core.hashindex import EMPTY, bucket_of
from repro.learn.policy import (ASSOC_CAP, DEFAULT_MLP, FEAT_SHIFT,
                                FREQ_CAP, H_SHIFT, RECENCY_CAP, W_SHIFT,
                                LearnedConfig, make_scorer, quantize,
                                score_rows)

CFGS = {
    "logreg": LearnedConfig(),
    "mlp": LearnedConfig(kind="mlp", weights=DEFAULT_MLP),
}


# ---------------------------------------------------------------------------
# Frozen oracle: scoring + scored insert/access in plain NumPy
# ---------------------------------------------------------------------------

def np_score_rows(cfg: LearnedConfig, recency, freq, assoc, pf_flag):
    """Bit-exact NumPy twin of ``repro.learn.policy.score_rows``."""
    q16 = 1 << FEAT_SHIFT
    rec = np.clip(recency, 0, RECENCY_CAP).astype(np.int32) \
        * np.int32(q16 // RECENCY_CAP)
    fr = np.clip(freq, 0, FREQ_CAP).astype(np.int32) \
        * np.int32(q16 // FREQ_CAP)
    ac = np.clip(assoc, 0, ASSOC_CAP).astype(np.int32) \
        * np.int32(q16 // ASSOC_CAP)
    pf = np.asarray(pf_flag).astype(np.int32) * np.int32(q16)
    f = (rec, fr, ac, pf)
    if cfg.kind == "logreg":
        *w, bias = cfg.weights
        s = np.full_like(f[0], quantize(bias) << FEAT_SHIFT)
        for wi, fi in zip(w, f):
            s = s + np.int32(quantize(wi)) * fi
        return s
    w1, b1, w2, b2 = cfg.weights
    s = np.full_like(f[0], quantize(b2) << (FEAT_SHIFT - H_SHIFT
                                            + W_SHIFT))
    for j in range(len(w1)):
        h = np.full_like(f[0], quantize(b1[j]) << FEAT_SHIFT)
        for wi, fi in zip(w1[j], f):
            h = h + np.int32(quantize(wi)) * fi
        h = np.maximum(h, 0)
        h = h >> H_SHIFT
        s = s + np.int32(quantize(w2[j])) * h
    return s


def np_state(state: base.CacheState) -> dict:
    return {f: np.asarray(getattr(state, f)).copy()
            for f in state._fields}


def np_insert(stt: dict, b: int, block: int, pf: int, src: int,
              hint: int, lcfg: LearnedConfig):
    """Scored ``_insert_rows`` with Python control flow; mutates ``stt``."""
    keys, stamps = stt["key"][b], stt["stamp"][b]
    flags, scs, srcs = stt["pf_flag"][b], stt["pf_sc"][b], stt["pf_src"][b]
    freqs, assocs = stt["freq"][b], stt["assoc"][b]
    clock = stt["clock"]
    empty = keys == EMPTY
    if empty.any():
        way = int(np.argmax(empty))
        ev = (int(EMPTY), False, PF_NONE)
    else:
        scores = np_score_rows(lcfg, clock - stamps, freqs, assocs, flags)
        v0 = int(np.argmin(scores))
        if flags[v0] == 1 and scs[v0] == 0:     # second chance
            stamps[v0] = clock
            scs[v0] = 1
            scores = scores.copy()
            scores[v0] = np.iinfo(np.int32).max
            way = int(np.argmin(scores))
        else:
            way = v0
        ev = (int(keys[way]), bool(flags[way] == 1), int(srcs[way]))
    keys[way], stamps[way], flags[way] = block, clock, pf
    scs[way], srcs[way] = 0, src
    freqs[way], assocs[way] = 1, hint
    return ev


def np_access(stt: dict, block: int, hint: int, lcfg: LearnedConfig):
    """Scored demand access (lru policy); mutates ``stt``."""
    stt["clock"] = stt["clock"] + 1
    b = int(bucket_of(jnp.int32(block), stt["key"].shape[0]))
    hits = stt["key"][b] == block
    if hits.any():
        way = int(np.argmax(hits))
        used = (int(stt["pf_src"][b, way])
                if stt["pf_flag"][b, way] == 1 else PF_NONE)
        stt["stamp"][b, way] = stt["clock"]
        stt["pf_flag"][b, way] = 0
        stt["pf_src"][b, way] = PF_NONE
        stt["freq"][b, way] += 1
        return True, used, (int(EMPTY), False, PF_NONE)
    ev = np_insert(stt, b, block, 0, PF_NONE, hint, lcfg)
    return False, PF_NONE, ev


def np_prefetch(stt: dict, block: int, src: int, hint: int,
                lcfg: LearnedConfig):
    """Scored prefetch insert; mutates ``stt``; returns (issued, ev)."""
    b = int(bucket_of(jnp.int32(block), stt["key"].shape[0]))
    if block == EMPTY or (stt["key"][b] == block).any():
        return False, (int(EMPTY), False, PF_NONE)
    return True, np_insert(stt, b, block, 1, src, hint, lcfg)


def assert_state_equal(got: base.CacheState, want: dict, msg: str):
    for f in got._fields:
        np.testing.assert_array_equal(np.asarray(getattr(got, f)),
                                      want[f], err_msg=f"{msg} leaf {f}")


# ---------------------------------------------------------------------------
# Property tests
# ---------------------------------------------------------------------------

ROWS = st.lists(
    st.tuples(st.integers(-2, 2 * RECENCY_CAP), st.integers(0, 3 * FREQ_CAP),
              st.integers(0, 2 * ASSOC_CAP), st.booleans()),
    min_size=1, max_size=16)

LOGREG_W = st.tuples(*(st.floats(-8.0, 8.0) for _ in range(5)))


@settings(max_examples=40, deadline=None)
@given(ROWS, LOGREG_W, st.sampled_from(sorted(CFGS)))
def test_score_rows_matches_numpy_oracle(rows, weights, kind):
    """Jitted scoring == NumPy scoring, bit for bit — for the checked-in
    defaults of both kinds AND arbitrary logreg weights."""
    rec, fr, ac, pf = (np.array(c, np.int32) for c in zip(*rows))
    cfgs = [CFGS[kind], LearnedConfig(weights=weights)]
    for cfg in cfgs:
        got = jax.jit(functools.partial(score_rows, cfg))(
            jnp.asarray(rec), jnp.asarray(fr), jnp.asarray(ac),
            jnp.asarray(pf))
        want = np_score_rows(cfg, rec, fr, ac, pf.astype(np.int32))
        assert np.asarray(got).dtype == np.int32
        np.testing.assert_array_equal(np.asarray(got), want,
                                      err_msg=f"kind={cfg.kind}")


# (block, is_prefetch, assoc_hint) over a tiny space: collisions,
# evictions, second chances and prefetch-hit consumption all fire
EVENTS = st.lists(
    st.tuples(st.integers(0, 40), st.booleans(), st.integers(0, 9)),
    min_size=1, max_size=60)


# jitted once per kind, like tests/test_amp_scatter._STEPS (the shim's
# @given wrapper hides the signature from pytest, so no fixtures here)
_STEPS = {
    name: (jax.jit(functools.partial(base.access, policy="lru",
                                     scorer=make_scorer(lcfg))),
           jax.jit(functools.partial(base.insert_prefetch,
                                     src=jnp.int32(PF_MITHRIL),
                                     enable=jnp.array(True),
                                     scorer=make_scorer(lcfg))),
           jax.jit(functools.partial(base.access, policy="lru",
                                     scorer=make_scorer(lcfg),
                                     enabled=jnp.array(False))))
    for name, lcfg in CFGS.items()
}


@settings(max_examples=10, deadline=None)
@given(EVENTS, st.sampled_from(sorted(CFGS)))
def test_scored_path_matches_numpy_oracle(events, kind):
    lcfg = CFGS[kind]
    access, prefetch, _ = _STEPS[kind]
    state = base.init_cache(capacity=32, ways=4)
    stt = np_state(state)
    for i, (blk, is_pf, hint) in enumerate(events):
        msg = f"kind={kind} event {i} ({blk}, pf={is_pf})"
        if is_pf:
            state, issued, ev = prefetch(state, jnp.int32(blk),
                                         assoc_hint=jnp.int32(hint))
            want_issued, want_ev = np_prefetch(stt, blk, PF_MITHRIL,
                                               hint, lcfg)
            assert bool(issued) == want_issued, msg
        else:
            state, hit, used, ev, _ = access(state, jnp.int32(blk),
                                          assoc_hint=jnp.int32(hint))
            want_hit, want_used, want_ev = np_access(stt, blk, hint, lcfg)
            assert bool(hit) == want_hit, msg
            assert int(used) == want_used, msg
        assert_state_equal(state, stt, msg)
        assert (int(ev.block), bool(ev.unused_pf), int(ev.pf_src)) \
            == want_ev, msg


@settings(max_examples=10, deadline=None)
@given(EVENTS, st.sampled_from(sorted(CFGS)))
def test_scored_access_disabled_is_noop(events, kind):
    """``enabled=False`` with a scorer is a bit-exact no-op — the
    padded-tail lane freeze of the sweep engine, unchanged by learned
    eviction (the learned configs also ride test_sweep's padded-suite
    sweep-vs-simulate pinning)."""
    access, _, dis = _STEPS[kind]
    state = base.init_cache(capacity=32, ways=4)
    for blk, _, hint in events:
        state, _, _, _, _ = access(state, jnp.int32(blk),
                                assoc_hint=jnp.int32(hint))
        frozen, hit, used, ev, _ = dis(state, jnp.int32(blk),
                                    assoc_hint=jnp.int32(hint))
        for f in state._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(frozen, f)),
                np.asarray(getattr(state, f)),
                err_msg=f"enabled=False mutated {f} on block {blk}")
        assert not bool(hit) and int(used) == PF_NONE
        assert int(ev.block) == int(EMPTY)


def test_learned_config_validation():
    with pytest.raises(ValueError):
        LearnedConfig(kind="tree")
    with pytest.raises(ValueError):
        LearnedConfig(weights=(1.0, 2.0))
    with pytest.raises(ValueError):
        LearnedConfig(kind="mlp", weights=(((1.0,),), (0.0,), (1.0,), 0.0))
    assert LearnedConfig().hidden == 0
    assert LearnedConfig(kind="mlp", weights=DEFAULT_MLP).hidden == 8
    # hashability is load-bearing: SimConfig is an lru_cache key
    assert hash(CFGS["mlp"]) == hash(LearnedConfig(kind="mlp",
                                                   weights=DEFAULT_MLP))
