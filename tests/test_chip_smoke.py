"""CPU rehearsal of ``chip_smoke.py`` and the kernel-side activations.

The smoke script's phase functions run here at the ``--reduced`` size with
interpret-mode kernels (the same code the chip runs at full size); its
``main()`` must refuse a CPU backend before any work, and a copy of the
script outside a checkout must refuse too.  The kernel forms of the ten
activations (kernels/epilogue.py) are held to the exact ``jax.numpy``
reference over ±20, value and derivative, through an interpret-mode
kernel.
"""
import json
import os
import pathlib
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
import chip_smoke  # noqa: E402

from repro.core.activations import ACTIVATION_ORDER, ACTIVATIONS  # noqa: E402
from repro.kernels.epilogue import KERNEL_ACTIVATIONS  # noqa: E402

REDUCED = ["--arch", "parallelmlp-10k", "--reduced", "--batch", "32",
           "--steps", "12", "--scan-steps", "4", "--ckpt-every", "0",
           "--seed", "0"]
REDUCED_DEEP = ["--arch", "parallelmlp-10k",
                "--population-depths", "24,12;16,8,4;9;8",
                "--population-repeats", "3", "--population-acts", "paper",
                "--population-block", "8", "--population-features", "10",
                "--optimizer", "adamw", "--bd-impl", "fused", "--batch",
                "32", "--steps", "12", "--scan-steps", "4",
                "--ckpt-every", "0", "--seed", "0"]

# kernel form vs exact reference: |diff| <= ATOL + RTOL·|ref| over ±20
# (the rational erf is good to ~4e-7 absolute; exp(min(x, 0)) − 1 loses
# at most an ulp of 1 against expm1 near 0)
ATOL, RTOL = 2e-6, 2e-6


def test_main_refuses_cpu_backend(capsys):
    assert jax.default_backend() == "cpu"
    assert chip_smoke.main([]) == 2
    out, err = capsys.readouterr()
    assert "TPU" in err
    assert "{" not in out          # no result line


def test_refuses_outside_a_checkout(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert not r.stdout.strip(), r.stdout


def test_phases_reduced_interpret():
    """Phases A-D at the reduced size: every check the chip run makes
    passes with interpret-mode kernels."""
    failures = chip_smoke.single_chip(
        paper=REDUCED, deep_argv=REDUCED_DEEP,
        serve_kw=dict(batch=8, n_req=24, n_calib=64))
    assert failures == []


def test_sharded_phase_four_cpu_devices():
    """The --chips 4 phase on four virtual CPU devices: the member-sharded
    fused run agrees with the one-device run."""
    script = (
        "import sys; sys.path[:0] = [sys.argv[1], sys.argv[1] + '/tests']\n"
        "import chip_smoke, test_chip_smoke as t\n"
        "f = chip_smoke.sharded_phase(t.REDUCED + chip_smoke.FUSED)\n"
        "assert f == [], f\n"
        "print('OK')\n")
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [str(ROOT / "src")] + env.get("PYTHONPATH", "").split(os.pathsep))
    r = subprocess.run([sys.executable, "-c", script, str(ROOT)], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert "[S] max per-member" in r.stdout and "OK" in r.stdout


def test_collective_counter():
    text = "\n".join([
        '  %ag = f32[8]{0} all-gather(f32[2]{0} %p), dimensions={0}',
        '  %k = f32[8]{0} custom-call(%ag), '
        'custom_call_target="tpu_custom_call"',
        '  %ags = (f32[2]{0}, f32[8]{0}) all-gather-start(%q)',
        '  %ar = f32[8]{0} all-reduce(%k), to_apply=%add'])
    assert chip_smoke.count_collectives(text) == {
        "all_gather": 2, "all_reduce": 1, "tpu_custom_call": 1}


def _kernel_eval(fn, x):
    def kernel(x_ref, o_ref):
        o_ref[...] = fn(x_ref[...])
    return pl.pallas_call(kernel, out_shape=jax.ShapeDtypeStruct(
        x.shape, jnp.float32), interpret=True)(x)


@pytest.mark.parametrize("name", ACTIVATION_ORDER)
def test_kernel_activation_matches_reference(name):
    fn, deriv = KERNEL_ACTIVATIONS[ACTIVATION_ORDER.index(name)]
    ref = ACTIVATIONS[name]
    x = jnp.linspace(-20.0, 20.0, 8 * 1024, dtype=jnp.float32).reshape(8, -1)
    # avoid the kinks: derivatives are compared off the exact breakpoints
    x = jnp.where(jnp.isin(x, jnp.asarray([0.0, 0.5, -0.5])), x + 1e-3, x)
    want = ref(x)
    want_d = jax.vmap(jax.vmap(jax.grad(ref)))(x)
    got = _kernel_eval(fn, x)
    got_d = _kernel_eval(deriv, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=RTOL, atol=ATOL)
    np.testing.assert_allclose(np.asarray(got_d), np.asarray(want_d),
                               rtol=RTOL, atol=ATOL)


def test_last_line_is_the_result_contract(monkeypatch, capsys):
    """On a (faked) TPU backend with the phases stubbed, the last stdout
    line is exactly the JSON result the driver reads."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(chip_smoke, "single_chip", lambda: [])
    assert chip_smoke.main([]) == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    d = jax.devices()[0]
    assert json.loads(last) == {"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}
