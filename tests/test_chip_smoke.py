"""chip_smoke.py off the chip: the rehearsal runs end to end and says
REHEARSAL, the real run refuses a CPU before doing any work, and the
compile cache sits where the environment (or the checkout) puts it."""
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(args, env_extra=None):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.update(env_extra or {})
    return subprocess.run([sys.executable] + args, env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=300)


def test_tiny_rehearsal_ends_in_rehearsal():
    out = _run([SMOKE, "--tiny"])
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    assert lines[-1] == "REHEARSAL"
    assert "PASS" not in out.stdout and '"ok": true, "device"' \
        not in out.stdout
    records = [json.loads(ln) for ln in lines[:-1]]
    assert all(r["platform"] == "cpu" for r in records)
    phases = [r["phase"] for r in records]
    assert phases == ["start", "census", "train_bert", "train_resnet",
                      "serve", "end"]
    by = {r["phase"]: r for r in records}
    assert by["train_bert"]["compiles_after_step_1"] == 0
    assert by["serve"]["compiles_after_warmup"] == 0
    census = by["census"]["kernels"]
    assert [k["kernel"] for k in census] == [
        "flash_attention", "paged_attention", "mla_paged_attention",
        "grouped_matmul"]
    assert all(k["use_pallas"] and k["interpret"] for k in census)


def test_real_run_refuses_a_cpu_before_any_work():
    out = _run([SMOKE])
    assert out.returncode != 0
    assert out.stdout.strip() == ""          # no result of any kind
    assert "needs a TPU" in out.stderr


def test_compile_cache_is_placed_from_outside(tmp_path):
    """An exported JAX_COMPILATION_CACHE_DIR is left alone (JAX reads it
    itself; nothing is set in code); without it the cache sits at one
    fixed path inside the checkout."""
    from mxnet_tpu.base import compile_cache_dir
    assert compile_cache_dir({"JAX_COMPILATION_CACHE_DIR": "/x"}) is None
    assert compile_cache_dir({}) == os.path.join(
        REPO, ".mxnet_tpu_cache", "xla")
    # and through a real import, in a process of its own
    want = str(tmp_path / "xla")
    out = _run(["-c", "import mxnet_tpu, jax; "
                "print(jax.config.jax_compilation_cache_dir)"],
               {"JAX_COMPILATION_CACHE_DIR": want})
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == want
