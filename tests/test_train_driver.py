"""launch.train population driver: depth-spec parsing errors, the
TrainRunner-backed loop's checkpoint behaviour (no duplicate final save),
and resume with a DIFFERENT requested layout (the checkpoint's layout
wins)."""
import jax
import numpy as np
import pytest

import repro.checkpoint as ckpt_mod
from repro.launch.train import main, parse_depth_spec


def test_parse_depth_spec():
    assert parse_depth_spec("64,32,16;13,5;7") == ((64, 32, 16), (13, 5),
                                                   (7,))
    # stray separators / whitespace are tolerated, not members
    assert parse_depth_spec(" 8 ; ;4,2 ") == ((8,), (4, 2))


@pytest.mark.parametrize("bad", ["", ";", " ; ; "])
def test_parse_depth_spec_empty_groups(bad):
    with pytest.raises(ValueError):
        parse_depth_spec(bad)


@pytest.mark.parametrize("bad", ["a", "8,b;4", "8;;4,2,x", "1.5"])
def test_parse_depth_spec_bad_ints(bad):
    with pytest.raises(ValueError):
        parse_depth_spec(bad)


def _run(tmp_path, steps, ckpt_every, extra=()):
    return main(["--arch", "parallelmlp-10k", "--reduced",
                 "--steps", str(steps), "--ckpt-every", str(ckpt_every),
                 "--ckpt-dir", str(tmp_path / "ck"),
                 "--population-depths", "8,4;8,4;6;5",
                 "--population-acts", "relu,tanh",
                 "--scan-steps", "2", "--samples", "256", *extra])


def test_no_duplicate_final_checkpoint(tmp_path, monkeypatch):
    """When the cadence already saved the final step, the after-loop save
    must not write it a second time (the old loop saved twice whenever
    steps %% ckpt_every == 0)."""
    calls = []
    orig = ckpt_mod.save_population

    def counting(*a, **kw):
        calls.append(a[1])
        return orig(*a, **kw)

    monkeypatch.setattr(ckpt_mod, "save_population", counting)
    # scan=2, ckpt_every=2 → the runner cadence saves every chunk (steps
    # 1,3,5,7); the final step 7 is already on disk, so the after-loop
    # save_population must NOT fire (the old loop wrote it twice).
    _run(tmp_path, steps=8, ckpt_every=2)
    assert calls == [], calls
    saved = ckpt_mod.latest_steps(str(tmp_path / "ck"))
    assert saved and saved[-1] == 7

    # cadence that does NOT land on the final step → exactly ONE final save
    _run(tmp_path, steps=12, ckpt_every=8, extra=["--resume"])
    assert calls == [11], calls
    saved = ckpt_mod.latest_steps(str(tmp_path / "ck"))
    assert saved[-1] == 11


def test_resume_prefers_checkpoint_layout(tmp_path):
    params, lp1 = _run(tmp_path, steps=4, ckpt_every=2)
    assert ckpt_mod.latest_steps(str(tmp_path / "ck"))
    # resume with a DIFFERENT --population-depths: the checkpoint's layout
    # must win (params and layout travel together)
    params2, lp2 = main([
        "--arch", "parallelmlp-10k", "--reduced", "--steps", "6",
        "--ckpt-every", "2", "--ckpt-dir", str(tmp_path / "ck"),
        "--population-depths", "32,16,8;3", "--population-acts", "gelu",
        "--scan-steps", "2", "--samples", "256", "--resume"])
    assert lp2 == lp1
    assert jax.tree_util.tree_structure(params2) == \
        jax.tree_util.tree_structure(params)


def test_resume_checkpoint_naming_removed_impls(tmp_path):
    """A checkpoint whose meta still records the removed ``act_impl`` and
    ``m3_impl`` options resumes: the run reads only what it still has."""
    import json
    import pathlib
    _run(tmp_path, steps=4, ckpt_every=2)
    for tree in pathlib.Path(tmp_path / "ck").glob("step_*/tree.json"):
        d = json.loads(tree.read_text())
        d["meta"]["train"].update(act_impl="pallas", m3_impl="scatter")
        tree.write_text(json.dumps(d))
    params, lp = _run(tmp_path, steps=6, ckpt_every=2, extra=["--resume"])
    assert all(np.isfinite(np.asarray(p)).all()
               for p in jax.tree.leaves(params))


@pytest.mark.parametrize("flag,value", [
    ("--bd-impl", "pallas"), ("--act-impl", "pallas"),
    ("--m3-impl", "scatter")])
def test_driver_refuses_removed_impls(flag, value, capsys):
    """``--bd-impl`` takes the two paths only, and the removed options are
    gone: each is refused before anything runs, with argparse's message."""
    with pytest.raises(SystemExit):
        main(["--arch", "parallelmlp-10k", "--reduced", flag, value])
    err = capsys.readouterr().err
    assert ("invalid choice: 'pallas'" in err if flag == "--bd-impl"
            else f"unrecognized arguments: {flag}" in err)


def test_driver_fused_bf16_halving_with_cheap_rungs(tmp_path):
    """The fused kernel + bf16 policy + subsampled rung evals compose with
    the halving lifecycle end to end: the driver prunes on schedule, the
    final leaderboard eval runs the full split, and the checkpoint meta
    records the training policy."""
    params, lp = _run(
        tmp_path, steps=6, ckpt_every=2,
        extra=["--bd-impl", "fused", "--compute-dtype", "bfloat16",
               "--halving", "2:0.5,4:0.5", "--rung-eval-batches", "1"])
    assert lp.num_real == 1                      # 4 → 2 → 1 members
    assert all(p.dtype == np.float32             # f32 masters checkpointed
               for p in jax.tree.leaves(params))
    meta, _ = ckpt_mod.load_meta(str(tmp_path / "ck"))
    assert meta["train"]["compute_dtype"] == "bfloat16"
    assert meta["train"]["bd_impl"] == "fused"
    # bd_impl is the one implementation decision a run records
    assert "act_impl" not in meta["train"] and "m3_impl" not in meta["train"]
    # the stateful-optimizer engine records its config too (sgd default)
    assert meta["train"]["optimizer"]["name"] == "sgd"


def test_resume_optimizer_mismatch_fails_loudly(tmp_path):
    """--resume must refuse to reinterpret a stored optimizer state tree
    under a different config: optimizer name AND hyperparameter changes
    both fail with the stored-vs-requested diff; the matching config
    resumes."""
    _run(tmp_path, steps=4, ckpt_every=2, extra=["--optimizer", "momentum"])
    with pytest.raises(ValueError, match="optimizer config mismatch"):
        _run(tmp_path, steps=8, ckpt_every=2,
             extra=["--optimizer", "adamw", "--resume"])
    with pytest.raises(ValueError, match="momentum"):
        _run(tmp_path, steps=8, ckpt_every=2,
             extra=["--optimizer", "momentum", "--momentum", "0.5",
                    "--resume"])
    # flipping a per-member flag is a different recipe too
    with pytest.raises(ValueError, match="per_member_momentum"):
        _run(tmp_path, steps=8, ckpt_every=2,
             extra=["--optimizer", "momentum", "--per-member-momentum",
                    "--resume"])
    params, lp = _run(tmp_path, steps=8, ckpt_every=2,
                      extra=["--optimizer", "momentum", "--resume"])
    assert lp.num_real == 4


def test_driver_checkpoints_opt_state_and_records_config(tmp_path):
    """Population checkpoints carry the optimizer state under 'extra'
    (momentum buffers on disk, restorable) and the full optimizer record
    under meta['train']['optimizer']."""
    import numpy as _np
    _run(tmp_path, steps=4, ckpt_every=2,
         extra=["--optimizer", "momentum", "--grad-clip", "1.0"])
    meta, step = ckpt_mod.load_meta(str(tmp_path / "ck"))
    rec = meta["train"]["optimizer"]
    assert rec["name"] == "momentum" and rec["momentum"] == 0.9
    assert rec["grad_clip"] == 1.0
    import os
    data = _np.load(os.path.join(str(tmp_path / "ck"),
                                 f"step_{step:08d}", "arrays.npz"))
    mu_keys = [k for k in data.files if k.startswith("extra/mu/")]
    assert mu_keys and any(_np.any(data[k]) for k in mu_keys)
    assert "extra/count" in data.files


def test_stateful_resume_equals_straight_run(tmp_path):
    """4 + 4 resumed MOMENTUM steps equal 8 uninterrupted ones — the
    restored momentum buffers carry the trajectory, so equality proves
    the opt-state checkpoint round-trip."""
    mom = ["--optimizer", "momentum", "--per-member-momentum"]
    _run(tmp_path, steps=4, ckpt_every=4, extra=mom)
    p_resumed, lp = _run(tmp_path, steps=8, ckpt_every=4,
                         extra=mom + ["--resume"])
    p_straight, lp2 = main([
        "--arch", "parallelmlp-10k", "--reduced", "--steps", "8",
        "--ckpt-every", "0", "--ckpt-dir", str(tmp_path / "ck2"),
        "--population-depths", "8,4;8,4;6;5", "--population-acts",
        "relu,tanh", "--scan-steps", "2", "--samples", "256", *mom])
    assert lp == lp2
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6),
        p_resumed, p_straight)
    # a different --seed would silently redraw the per-member vectors
    # beneath the restored moments — the config guard catches it
    with pytest.raises(ValueError, match="seed"):
        _run(tmp_path, steps=12, ckpt_every=4,
             extra=mom + ["--resume", "--seed", "1"])


def test_driver_flag_validation():
    with pytest.raises(SystemExit):
        main(["--arch", "parallelmlp-10k", "--reduced", "--steps", "1",
              "--per-member-momentum"])          # needs --optimizer momentum
    with pytest.raises(SystemExit):
        main(["--arch", "parallelmlp-10k", "--reduced", "--steps", "1",
              "--optimizer", "adamw", "--per-member-weight-decay"])  # wd=0
    with pytest.raises(SystemExit):   # would be silently ignored otherwise
        main(["--arch", "parallelmlp-10k", "--reduced", "--steps", "1",
              "--optimizer", "momentum", "--opt-state-dtype", "bfloat16"])


def test_resume_continues_training(tmp_path):
    """4 + 4 resumed steps equal 8 uninterrupted steps (step-indexed data,
    layout-carrying checkpoints)."""
    _run(tmp_path, steps=4, ckpt_every=4)
    p_resumed, lp = _run(tmp_path, steps=8, ckpt_every=4,
                         extra=["--resume"])
    p_straight, lp2 = main([
        "--arch", "parallelmlp-10k", "--reduced", "--steps", "8",
        "--ckpt-every", "0", "--ckpt-dir", str(tmp_path / "ck2"),
        "--population-depths", "8,4;8,4;6;5", "--population-acts",
        "relu,tanh", "--scan-steps", "2", "--samples", "256"])
    assert lp == lp2
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6),
        p_resumed, p_straight)


def test_compile_cache_directory(monkeypatch):
    """The entry points keep JAX's compile cache where
    JAX_COMPILATION_CACHE_DIR says and set no other directory; unset, it
    goes to the fixed <checkout>/.jax_cache, which git ignores."""
    import pathlib

    from repro.launch.cache import DEFAULT_DIR, configure_compile_cache
    was = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert configure_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert configure_compile_cache() == str(DEFAULT_DIR)
        assert jax.config.jax_compilation_cache_dir == str(DEFAULT_DIR)
        root = pathlib.Path(__file__).resolve().parents[1]
        assert DEFAULT_DIR == root / ".jax_cache"
        assert ".jax_cache/" in (root / ".gitignore").read_text().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
