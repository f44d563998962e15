"""Tools tests: im2rec, launch, opperf (reference: ``tools/`` +
``benchmark/opperf``)."""
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _make_images(root):
    from PIL import Image
    rng = np.random.RandomState(0)
    for cls in ("a", "b"):
        os.makedirs(os.path.join(root, cls), exist_ok=True)
        for i in range(4):
            Image.fromarray(
                rng.randint(0, 255, (24, 30, 3), dtype=np.uint8)).save(
                os.path.join(root, cls, "img%d.jpg" % i))


def test_im2rec_list_and_pack(tmp_path):
    root = str(tmp_path / "imgs")
    prefix = str(tmp_path / "ds")
    _make_images(root)
    from tools import im2rec
    im2rec.main([prefix, root, "--list"])
    assert os.path.exists(prefix + ".lst")
    im2rec.main([prefix + ".lst", root, "--resize", "16",
                 "--center-crop"])
    from mxnet_tpu import recordio
    rec = recordio.MXIndexedRecordIO(prefix + ".idx", prefix + ".rec",
                                     "r")
    assert len(rec.keys) == 8
    hdr, img = recordio.unpack_img(rec.read_idx(rec.keys[0]))
    assert img.shape == (16, 16, 3)
    assert hdr.label in (0.0, 1.0)


def test_launch_local_env():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "launch.py"),
         "-n", "2", sys.executable, "-c",
         "import os; print(os.environ['MXNET_TPU_PROC_ID'],"
         "os.environ['MXNET_TPU_NUM_PROCS'])"],
        capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    # the launcher relays each worker line atomically with a "[rank] "
    # prefix (dmlc tracker behavior), so lines can never interleave
    lines = out.stdout.strip().splitlines()
    assert all(line.startswith("[") for line in lines), lines
    ranks = sorted(line.split()[1] for line in lines)
    assert ranks == ["0", "1"]
    prefixes = sorted(line.split()[0] for line in lines)
    assert prefixes == ["[0]", "[1]"]


def test_launch_local_refuses_a_tpu_host(monkeypatch):
    """A chip belongs to one process: on a host with TPU device nodes
    local mode starts nothing unless the workers are pinned to the CPU
    (the launcher looks at /dev, never at JAX)."""
    from tools import launch
    monkeypatch.setattr(
        launch.glob, "glob",
        lambda pat: ["/dev/vfio/0", "/dev/vfio/1"] if "vfio" in pat else [])
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    assert "one process" in launch._refuse_local_on_tpu_host()
    with pytest.raises(SystemExit) as exc:
        launch.main(["-n", "2", sys.executable, "-c", "pass"])
    assert exc.value.code == 2
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert launch._refuse_local_on_tpu_host() is None
    assert "jax" not in launch.__dict__          # holds no chip itself


def test_opperf_runs():
    from benchmark import opperf
    results = opperf.run(ops=["relu", "dot"], warmup=1, runs=2)
    by_op = {r["op"]: r for r in results}
    assert "avg_us" in by_op["relu"] and "avg_us" in by_op["dot"]


def test_distributed_init_noop_single_process(monkeypatch):
    import mxnet_tpu as mx
    monkeypatch.delenv("MXNET_TPU_COORDINATOR", raising=False)
    assert mx.distributed_init() is False


def test_program_diff_leaves_out_what_names_the_source():
    """tools/program_diff.py compares instructions: the source tables
    and an instruction's metadata may differ, an instruction may not."""
    from tools import program_diff

    def module(line, op="add"):
        return ("HloModule m\n\nFileNames\n1 \"%s.py\"\n\nStackFrames\n"
                "1 {file_location_id=1 parent_frame_id=1}\n\n"
                "ENTRY %%main (x: f32[]) -> f32[] {\n"
                "  %%x = f32[] parameter(0), metadata={op_name=\"h0/q\" "
                "source_file=\"%s.py\" source_line=%d}, stack_frame_id=1\n"
                "  ROOT %%y = f32[] %s(%%x, %%x)\n}\n" % (line, line, len(line),
                                                         op))
    same = program_diff._instructions(module("a"))
    assert program_diff._instructions(module("parent/b")) == same
    assert "source" not in same and "FileNames" not in same
    assert program_diff._instructions(module("a", "multiply")) != same


def test_program_diff_reads_a_checkout_like_itself(capsys):
    """A serving cell's programs compiled for a described v5e in this
    checkout and (a process of its own) in the same one: the same."""
    from tools import program_diff
    try:
        from jax.experimental import topologies
        topologies.get_topology_desc(platform="tpu",
                                     topology_name="v5e:2x2")
    except Exception as e:          # pragma: no cover
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    assert program_diff.main(["--workload", "gpt2m_serve_closed16",
                              "--other", REPO, "--rehearse"]) == 0
    lines = capsys.readouterr().out.split("\n")
    assert [ln.split()[1] for ln in lines if ln] == ["same"] * 3
