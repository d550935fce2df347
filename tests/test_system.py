"""End-to-end system tests: real training runs with restart + the
dry-run/roofline machinery at miniature scale."""

import jax
import numpy as np
import pytest

from repro.launch.train import train


@pytest.mark.slow
def test_train_checkpoint_restart_continuity(tmp_path):
    """Train 12 steps, 'crash', resume from ckpt, finish — losses finite
    and the resumed run continues from the checkpointed step."""
    kw = dict(steps=12, batch=2, seq=64, ckpt_dir=str(tmp_path),
              ckpt_every=5, log_every=100, seed=3)
    out1 = train("llama3.2-3b", **{**kw, "steps": 7})   # stops after 7
    assert all(np.isfinite(out1["losses"]))
    out2 = train("llama3.2-3b", **kw)                    # resumes at 5
    assert len(out2["losses"]) == 12 - 5
    assert all(np.isfinite(out2["losses"]))


@pytest.mark.slow
def test_train_with_compression_converges_similarly(tmp_path):
    a = train("llama3.2-3b", steps=8, batch=2, seq=64,
              ckpt_dir=str(tmp_path / "a"), resume=False, log_every=100)
    b = train("llama3.2-3b", steps=8, batch=2, seq=64, compress=True,
              ckpt_dir=str(tmp_path / "b"), resume=False, log_every=100)
    assert abs(a["final_loss"] - b["final_loss"]) < 0.3


def test_input_specs_cover_all_cells():
    from repro.configs import all_cells
    from repro.launch.specs import input_specs
    n = 0
    for arch, shape, on, why in all_cells():
        n += 1
        if not on:
            assert why
            continue
        specs = input_specs(arch, shape)
        assert "params" in specs
        if shape.kind == "train":
            assert specs["batch"]["labels"].shape == \
                (shape.global_batch, shape.seq_len)
        if shape.kind == "decode":
            assert specs["token"].shape == (shape.global_batch,)
    assert n == 40


def test_parse_collectives():
    from repro.launch.dryrun import parse_collectives
    hlo = """
  %ag = bf16[8,128]{1,0} all-gather(%x), dimensions={1}
  %ar.1 = f32[16]{0} all-reduce-start(%y), to_apply=%sum
  %d = bf16[8,128]{1,0} dot(%a, %b)
  %rs = f32[4,4]{1,0} reduce-scatter(%z), dimensions={0}
"""
    by_kind, counts = parse_collectives(hlo)
    assert by_kind["all-gather"] == 8 * 128 * 2
    assert by_kind["all-reduce"] == 16 * 4
    assert by_kind["reduce-scatter"] == 16 * 4
    assert counts["all-gather"] == 1


def test_jit_cell_compiles_on_smoke_mesh(monkeypatch):
    """The dry-run path end-to-end on a 1-device mesh with a tiny arch."""
    import dataclasses
    import repro.configs as C
    from repro.configs import ShapeSpec, reduced_config
    from repro.launch.specs import input_specs
    from repro.launch.mesh import auto_mesh
    from repro.launch.steps import jit_cell

    tiny = dataclasses.replace(reduced_config(C.ARCHS["llama3.2-3b"]),
                               name="tiny-test")
    monkeypatch.setitem(C.ARCHS, "tiny-test", tiny)
    shape = ShapeSpec("t", 64, 2, "train")
    mesh = auto_mesh((1, 1), ("data", "model"))
    specs = input_specs("tiny-test", shape)
    jfn, args = jit_cell(mesh, specs)
    with jax.set_mesh(mesh):
        compiled = jfn.lower(*args).compile()
    from repro.launch.dryrun import cost_analysis_dict
    assert cost_analysis_dict(compiled).get("flops", 0) > 0


def test_compile_cache_location(monkeypatch):
    """The persistent compile cache goes where ``JAX_COMPILATION_CACHE_DIR``
    says (JAX reads the variable itself), else to ``<repo>/.jax_cache``."""
    from repro.launch import compile_cache as cc
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(cc.ENV_VAR, "/cache/from/env")
        assert cc.enable_compile_cache() == "/cache/from/env"
        assert jax.config.jax_compilation_cache_dir == was
        monkeypatch.delenv(cc.ENV_VAR)
        assert cc.enable_compile_cache() == str(cc.REPO_CACHE)
        assert jax.config.jax_compilation_cache_dir == str(cc.REPO_CACHE)
        assert (cc.REPO_CACHE.parent / "src" / "repro").is_dir()
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
