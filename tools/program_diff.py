"""Are a serving cell's compiled programs the same in two checkouts?

    python tools/program_diff.py --workload kimi_k2_serve_closed32 \
        --other build/parent [--blocks 257] [--rehearse]

Compiles, for a DESCRIBED TPU v5e (no chip: ``jax.experimental.
topologies``), every decode bucket and the largest prefill bucket of the
cell's engine as ``DecodeEngine`` builds them (the slabs donated), once
with this checkout's program and once with ``--other``'s, each in a
process of its own, and compares the optimized HLO instruction for
instruction (what names the source left out, in the kernels' bodies
too).  Weights
are shapes only and the cache is cut to ``--blocks`` blocks a layer: the
same cut on both sides, so the comparison is of the code and not of the
pool.  Prints one line a program and exits 1 where one differs.  What a
refactor that claims to leave the programs as they were runs to show it
(PR 38: the MLA bodies of ``latent_moe.py`` moved into methods of their
own)."""
import argparse
import hashlib
import json
import os
import re
import subprocess
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class _Capture:
    """Stands in for ``ModelRegistry``: keeps what a family's ``deploy``
    hands ``register_generative``."""

    def register_generative(self, name, model, **kw):
        self.model, self.kw = model, kw


def fingerprints(workload, blocks, rehearse=False):
    """{program: sha256 of its optimized HLO without metadata} of the
    cell's engine, compiled for a described v5e, in THIS process's
    checkout."""
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from mxnet_tpu.kernels import registry
    from mxnet_tpu.serving.decode import DecodeEngine
    from perfbench.harness.spec import Cell, sized
    registry._backend = lambda: "tpu"
    one = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    cell = Cell(os.getcwd(), workload)
    cfg = sized(cell.config, rehearse)
    fam = cell.family()
    built = {}

    def draw():
        built["model"], params = fam.build_model(cfg, 0)
        return params
    params = jax.eval_shape(draw)
    capture = _Capture()
    fam.deploy(capture, "diff", built["model"], params, cfg)
    kw = {k: v for k, v in capture.kw.items()
          if k not in ("params", "warmup")}
    kw["num_blocks"] = min(kw["num_blocks"], blocks)
    if kw.get("window_blocks"):
        kw["window_blocks"] = min(kw["window_blocks"], blocks)
    eng = DecodeEngine(capture.model, params, **kw)
    prefill, decode = eng._specs()
    programs = [("decode_b%d" % s, eng._decode_impl, decode[s])
                for s in sorted(decode)]
    top = max(prefill)
    programs.append(("prefill_b%d" % top, eng._prefill_impl, prefill[top]))
    out = {}
    for name, fn, specs in programs:
        specs = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
            specs)
        text = jax.jit(fn, donate_argnums=eng._DONATED).lower(
            *specs).compile().as_text()
        out[name] = hashlib.sha256(_instructions(text).encode()).hexdigest()
    return out


def _instructions(text):
    """The module's computations without what names the source: the
    tables of files, functions and stack frames ahead of the first
    computation, every instruction's metadata, and the locations inside
    each Pallas kernel's serialized body (they name the caller's
    functions)."""
    lines = text.splitlines()
    first = next(i for i, line in enumerate(lines)
                 if line.startswith(("%", "ENTRY")))
    body = "\n".join(lines[:1] + lines[first:])
    body = re.sub(r",? metadata=\{[^}]*\}", "", body)
    body = re.sub(r",? stack_frame_id=\d+", "", body)
    return re.sub(r'"body":"([^"]+)"', _kernel_body, body)


def _kernel_body(match):
    """A Mosaic kernel's MLIR bytecode -> the hash of its text without
    locations."""
    import base64

    from jax._src.interpreters import mlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir
    ctx = mlir.make_ir_context()
    tpu.register_dialect(ctx)
    ctx.allow_unregistered_dialects = True
    with ctx:
        module = ir.Module.parse(base64.b64decode(match.group(1)))
        asm = module.operation.get_asm(enable_debug_info=False)
    return '"body":"%s"' % hashlib.sha256(asm.encode()).hexdigest()


def _in_checkout(root, args):
    """``fingerprints`` in a process whose program is ``root``'s."""
    env = dict(os.environ, PYTHONPATH=root, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, os.path.abspath(__file__), "--workload",
           args.workload, "--blocks", str(args.blocks), "--one"] \
        + (["--rehearse"] if args.rehearse else [])
    done = subprocess.run(cmd, cwd=root, env=env, capture_output=True,
                          text=True)
    if done.returncode:
        raise SystemExit("program_diff: %s failed:\n%s"
                         % (root, done.stderr[-3000:]))
    return json.loads(done.stdout.strip().splitlines()[-1])


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--other", help="the checkout to compare with")
    p.add_argument("--blocks", type=int, default=257)
    p.add_argument("--rehearse", action="store_true")
    p.add_argument("--one", action="store_true", help=argparse.SUPPRESS)
    args = p.parse_args(argv)
    if args.one:
        print(json.dumps(fingerprints(args.workload, args.blocks,
                                      args.rehearse)))
        return 0
    ours = _in_checkout(HERE, args)
    theirs = _in_checkout(os.path.abspath(args.other), args)
    differ = 0
    for name in sorted(set(ours) | set(theirs)):
        same = ours.get(name) == theirs.get(name)
        differ += not same
        print("%s %s %s" % (name, "same" if same else "DIFFERS",
                            ours.get(name, "-")[:16]))
    return 1 if differ else 0


if __name__ == "__main__":
    sys.exit(main())
