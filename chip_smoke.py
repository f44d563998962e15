"""chip_smoke.py -- the quickest proof that the system still starts on the chip.

One process drives the normal entry points once, at the full width of
the models the repo supports, and checks what comes out:

- ``census``: every registered Pallas kernel, compiled (not
  interpreted) at one real shape and compared with its XLA reference,
  beside the registry's decision for that shape;
- ``train_bert``: BERT-base, ``gluon`` -> ``Trainer`` -> ``TrainStep``
  under bf16 AMP, host batches staged through ``DeviceFeed``;
- ``train_resnet``: ResNet-50 v1, one ``TrainStep.run_steps`` dispatch;
- ``serve``: a 12-layer 768-wide decoder behind
  ``ModelRegistry.register_generative``, eight concurrent streams,
  judged against the float32 full forward.

``python chip_smoke.py`` needs a TPU and exits non-zero without one.
``--chips 4`` runs the data-parallel BERT trainer over four chips
instead.  ``--tiny`` is a CPU rehearsal of the same code at toy sizes;
it ends in ``REHEARSAL`` and proves nothing about the chip.

Every line printed is one JSON object stamped with the device.  The
last line of a passing chip run is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Any phase that raises ends the process with a non-zero exit code.
Wall times printed here are smoke timings, not performance.
"""
import argparse
import collections
import gc
import json
import logging
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

# Published widths; only the tiny rehearsal shrinks them.
FULL = {
    "bert": {"vocab": 30522, "units": 768, "hidden": 3072, "layers": 12,
             "heads": 12, "batch": 32, "seq": 512, "steps": 6},
    "resnet": {"net": "resnet50_v1", "batch": 128, "image": 224,
               "classes": 1000, "k": 4},
    "gpt": {"vocab": 30522, "units": 768, "layers": 12, "heads": 12,
            "max_seq": 512, "prompt_lens": (3, 16, 17, 40, 64, 100, 128, 5),
            "max_new": 32, "kv_blocks": None},
    "census": {"flash": (32 * 12, 512, 64),          # BERT-base seq 512
               "paged": {"slots": 8, "heads": 12, "d": 64, "blocks": 512,
                         "block": 16, "table": 32},
               # Kimi-K2's latent row: 512 + 64 values in 640 lanes
               "latent": {"slots": 16, "heads": 64, "lanes": 640,
                          "v_width": 512, "blocks": 1025, "block": 64,
                          "table": 64},
               # Mellum2's expert layer: a chunk of a prefill's sorted rows
               "grouped": {"rows": 2048, "groups": 64, "k": 2304,
                           "n": 896}},
    "dp": {"chips": 4, "batch": 128, "steps": 3},
}
TINY = {
    "bert": {"vocab": 128, "units": 64, "hidden": 128, "layers": 1,
             "heads": 2, "batch": 2, "seq": 32, "steps": 4},
    "resnet": {"net": "one bottleneck per stage", "batch": 2, "image": 32,
               "classes": 10, "k": 2},
    "gpt": {"vocab": 128, "units": 32, "layers": 2, "heads": 2,
            "max_seq": 64, "prompt_lens": (3, 16, 17, 9), "max_new": 6,
            "kv_blocks": 64},
    "census": {"flash": (4, 32, 16),
               "paged": {"slots": 2, "heads": 2, "d": 16, "blocks": 12,
                         "block": 4, "table": 5},
               "latent": {"slots": 2, "heads": 2, "lanes": 128,
                          "v_width": 16, "blocks": 12, "block": 4,
                          "table": 4},
               "grouped": {"rows": 40, "groups": 64, "k": 32, "n": 16}},
    "dp": {"chips": 4, "batch": 8, "steps": 3},
}

# The chip multiplies float32 matrices in one bf16 pass by default; the
# float32 reference is taken at "highest".  Measured gap on a v5e at
# these widths: max 0.37 on logits of std 2.2 (PERF.md, PR 21).
LOGIT_TOL = 0.75
# Kernel vs XLA reference, as a share of the reference's largest value
# (bf16 outputs carry 8 mantissa bits).
KERNEL_TOL = 3e-2
LOSS_TOL = 5e-2


# The programs a run exists to compile: the train step, the K-step
# scan, and the serving executables (``jit_mx_<kind>_b<bucket>``, one a
# prefill and a decode bucket).  A second run in the same call must find
# every one of them in the persistent cache.
MAIN_PROGRAMS = ("jit_step_fn", "jit_scan_fn", "jit_mx_")


class CacheLog(logging.Filter):
    """Which programs the persistent compile cache held.  JAX names the
    program of a lookup only in its compiler log, at DEBUG
    (``jax.monitoring`` counts hits, and of the misses only the entries
    written -- a program that compiles in about the write threshold of
    one second flips in and out of that count between runs).  A filter,
    so the DEBUG records read here go no further."""

    def __init__(self):
        super().__init__()
        self.lookups = collections.Counter()     # (program, "hit"|"miss")
        log = logging.getLogger("jax._src.compiler")
        self.pass_level = log.getEffectiveLevel()
        log.addFilter(self)
        log.setLevel(logging.DEBUG)

    def filter(self, record):
        msg = str(record.msg)
        if msg.startswith("Persistent compilation cache hit"):
            self.lookups[(record.args[0], "hit")] += 1
        elif msg.startswith("PERSISTENT COMPILATION CACHE MISS"):
            self.lookups[(record.args[0], "miss")] += 1
        return record.levelno >= self.pass_level

    def summary(self, since=None):
        """Lookups since the ``since`` snapshot: totals, and per main
        program."""
        seen = self.lookups - since if since else self.lookups
        main = {}
        for (name, kind), n in seen.items():
            if name.startswith(MAIN_PROGRAMS):
                main.setdefault(name, {"hit": 0, "miss": 0})[kind] = n
        return {"cache_hits": sum(n for (_p, k), n in seen.items()
                                  if k == "hit"),
                "cache_misses": sum(n for (_p, k), n in seen.items()
                                    if k == "miss"),
                "main_programs": main}


class Smoke:
    """Device stamp, line printing and compile counting for one run."""

    def __init__(self, tiny):
        dev = jax.devices()[0]
        self.stamp = {"platform": dev.platform,
                      "device_kind": dev.device_kind,
                      "device_count": len(jax.devices())}
        self.tiny = tiny
        self.compiles = 0            # compile requests, cached or not
        self.cache_log = CacheLog()
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_duration(self, event, _secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def line(self, **fields):
        print(json.dumps({**self.stamp, **fields}), flush=True)

    def peak_bytes(self):
        stats = jax.devices()[0].memory_stats() or {}
        return stats.get("peak_bytes_in_use")

    def phase(self, name, fn, *args):
        compiles = self.compiles
        looked_up = collections.Counter(self.cache_log.lookups)
        t0 = time.perf_counter()
        info = fn(self, *args)
        gc.collect()
        self.line(phase=name, ok=True,
                  wall_s=round(time.perf_counter() - t0, 1),
                  compiles=self.compiles - compiles,
                  **self.cache_log.summary(looked_up),
                  peak_bytes_in_use=self.peak_bytes(), **info)


def check(cond, what):
    if not cond:
        raise AssertionError(what)


def cache_state():
    path = jax.config.jax_compilation_cache_dir
    entries = len(os.listdir(path)) if path and os.path.isdir(path) else 0
    return {"cache_dir": path, "cache_entries": entries}


def rel_err(got, ref):
    got = jnp.asarray(got, jnp.float32)
    ref = jnp.asarray(ref, jnp.float32)
    return float(jnp.max(jnp.abs(got - ref))
                 / jnp.maximum(jnp.max(jnp.abs(ref)), 1.0))


# ----------------------------------------------------------------------
# kernel census
# ----------------------------------------------------------------------

def phase_census(smoke, cfg):
    """Compile and run each kernel once where the registry would, and
    hold it to its XLA reference.  A kernel the registry selects on
    this backend that does not compile raises out of here.  Off the
    chip the registry picks XLA everywhere, so the rehearsal asks for
    the kernels the way the repo's tests do (``force=True`` /
    ``use_pallas=True``: the kernel bodies run in interpret mode)."""
    from mxnet_tpu import kernels
    from mxnet_tpu.kernels.grouped_matmul import grouped_matmul
    from mxnet_tpu.kernels.mla_paged_attention import mla_paged_attention
    from mxnet_tpu.kernels.paged_attention import paged_attention
    from mxnet_tpu.ops.pallas.flash_attention import (
        flash_attention_bwd_pallas, flash_attention_fwd_pallas)

    force = True if smoke.tiny else None
    rng = np.random.RandomState(0)
    rows = []

    def record(name, choice, ran, err=None):
        rows.append({"kernel": name, "use_pallas": choice.use_pallas,
                     "interpret": choice.interpret,
                     "reason": choice.reason, "ran": ran,
                     "rel_err": None if err is None else round(err, 5)})
        if err is not None:
            check(err <= KERNEL_TOL,
                  "%s differs from its XLA reference by %.4f" % (name, err))

    # flash attention, forward and backward
    bh, seq, d = cfg["flash"]
    scale = 1.0 / d ** 0.5
    q, k, v = (jnp.asarray(rng.randn(bh, seq, d), jnp.bfloat16)
               for _ in range(3))
    ch = kernels.choose("flash_attention", force=force, seq=seq,
                        block_q=256, block_k=256)
    if ch.use_pallas:
        ref_fn = kernels.get("flash_attention").xla_ref

        def ref_sum(q, k, v):
            return jnp.sum(ref_fn(q, k, v, causal=False, scale=scale)
                           .astype(jnp.float32))
        out, lse = flash_attention_fwd_pallas(
            q, k, v, scale=scale, interpret=ch.interpret)
        err = rel_err(out, ref_fn(q, k, v, causal=False, scale=scale))
        dout = jnp.ones_like(out)
        delta = jnp.sum(out.astype(jnp.float32), axis=-1)
        grads = flash_attention_bwd_pallas(
            q, k, v, lse, dout, delta, scale=scale,
            interpret=ch.interpret)
        refs = jax.grad(ref_sum, argnums=(0, 1, 2))(q, k, v)
        err = max([err] + [rel_err(g, r) for g, r in zip(grads, refs)])
        record("flash_attention", ch, "fwd+bwd", err)
    else:
        record("flash_attention", ch, None)

    # paged attention at the serve phase's cache geometry
    pg = cfg["paged"]
    qd = jnp.asarray(rng.randn(pg["slots"], pg["heads"], pg["d"]),
                     jnp.float32)
    slab = (pg["blocks"], pg["block"], pg["heads"], pg["d"])
    kc = jnp.asarray(rng.randn(*slab), jnp.float32)
    vc = jnp.asarray(rng.randn(*slab), jnp.float32)
    bt = jnp.asarray(rng.randint(1, pg["blocks"],
                                 (pg["slots"], pg["table"])), jnp.int32)
    cl = jnp.asarray(rng.randint(1, pg["table"] * pg["block"],
                                 (pg["slots"], 1)), jnp.int32)
    ch = kernels.choose("paged_attention", force=force,
                        heads=pg["heads"], head_dim=pg["d"],
                        block_size=pg["block"])
    if ch.use_pallas:
        out = paged_attention(qd, kc, vc, bt, cl, scale=0.125,
                              use_pallas=force)
        ref = kernels.get("paged_attention").xla_ref(
            qd, kc, vc, bt, cl, scale=0.125)
        record("paged_attention", ch, "decode", rel_err(out, ref))
    else:
        record("paged_attention", ch, None)

    # latent (MLA) paged attention: rows that are keys and values at once
    lt = cfg["latent"]
    ql = jnp.asarray(rng.randn(lt["slots"], lt["heads"], lt["lanes"]),
                     jnp.bfloat16)
    rows_l = jnp.asarray(rng.randn(lt["blocks"], lt["block"], lt["lanes"]),
                         jnp.bfloat16)
    bt = jnp.asarray(rng.randint(1, lt["blocks"],
                                 (lt["slots"], lt["table"])), jnp.int32)
    cl = jnp.asarray(rng.randint(1, lt["table"] * lt["block"],
                                 (lt["slots"], 1)), jnp.int32)
    ch = kernels.choose("mla_paged_attention", force=force,
                        heads=lt["heads"], lanes=lt["lanes"],
                        v_width=lt["v_width"], block_size=lt["block"])
    if ch.use_pallas:
        out = mla_paged_attention(ql, rows_l, bt, cl, lt["v_width"],
                                  scale=0.04, use_pallas=force)
        ref = kernels.get("mla_paged_attention").xla_ref(
            ql, rows_l, bt, cl, lt["v_width"], scale=0.04)
        record("mla_paged_attention", ch, "decode", rel_err(out, ref))
    else:
        record("mla_paged_attention", ch, None)

    # grouped matmul: sorted rows, some groups empty, rows past the last
    gr = cfg["grouped"]
    lhs = jnp.asarray(rng.randn(gr["rows"], gr["k"]), jnp.bfloat16)
    rhs = jnp.asarray(rng.randn(gr["groups"], gr["k"], gr["n"]),
                      jnp.bfloat16)
    sizes = rng.multinomial(gr["rows"] * 7 // 8,
                            rng.dirichlet(np.ones(gr["groups"]) * 0.5))
    sizes = jnp.asarray(sizes, jnp.int32)
    ch = kernels.choose("grouped_matmul", force=force,
                        groups=gr["groups"], k=gr["k"], n=gr["n"])
    if ch.use_pallas:
        out = grouped_matmul(lhs, rhs, sizes, use_pallas=force)
        ref = kernels.get("grouped_matmul").xla_ref(lhs, rhs, sizes)
        record("grouped_matmul", ch, "prefill chunk", rel_err(out, ref))
    else:
        record("grouped_matmul", ch, None)
    return {"kernels": rows}


# ----------------------------------------------------------------------
# training
# ----------------------------------------------------------------------

def seed_everything(mx):
    """The weights come from a seed: gluon's initializers draw from
    numpy's global stream, everything else from ``mx.random``."""
    np.random.seed(0)
    mx.random.seed(0)


def build_bert(cfg, ctx):
    """(net, loss block, ids, labels): the published model-zoo entry at
    full size, the same block class cut down for the rehearsal."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon.model_zoo.bert import BERTModel
    seed_everything(mx)
    if (cfg["units"], cfg["layers"], cfg["heads"]) == (768, 12, 12):
        net = gluon.model_zoo.bert_base(vocab_size=cfg["vocab"],
                                        max_length=cfg["seq"], dropout=0.0)
    else:
        net = BERTModel(vocab_size=cfg["vocab"], units=cfg["units"],
                        hidden_size=cfg["hidden"],
                        num_layers=cfg["layers"], num_heads=cfg["heads"],
                        max_length=cfg["seq"], dropout=0.0)
    net.initialize(ctx=ctx)
    net.hybridize()
    vocab = cfg["vocab"]
    ce = gluon.loss.SoftmaxCrossEntropyLoss()

    class MLMLoss(gluon.HybridBlock):
        def hybrid_forward(self, F, outs, labels):
            mlm, _nsp = outs
            return ce(mlm.reshape((-1, vocab)), labels.reshape((-1,)))

    return net, MLMLoss()


def bert_batch(cfg, batch):
    rng = np.random.RandomState(0)
    shape = (batch, cfg["seq"])
    return (rng.randint(0, cfg["vocab"], shape).astype(np.float32),
            rng.randint(0, cfg["vocab"], shape).astype(np.float32))


def compiled_text(step, shardings=None):
    """Optimized HLO of the TrainStep's last program.  ``shardings``
    (data, label, everything else) restores what the abstract argument
    record drops, so the lookup compiles the program that ran."""
    fn, args = step._last_call
    if shardings is not None:
        data_sh, label_sh, rep = shardings
        args = list(jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep),
            args))
        args[2] = jax.ShapeDtypeStruct(args[2].shape, args[2].dtype,
                                       sharding=data_sh)
        args[3] = jax.ShapeDtypeStruct(args[3].shape, args[3].dtype,
                                       sharding=label_sh)
    return fn.lower(*args).compile().as_text()


def on_platform(array, platform):
    return all(d.platform == platform for d in array.devices())


def phase_train_bert(smoke, cfg):
    import mxnet_tpu as mx
    from mxnet_tpu import amp, gluon, kernels
    from mxnet_tpu.dataio import DeviceFeed
    from mxnet_tpu.parallel import TrainStep

    platform = smoke.stamp["platform"]
    ctx = mx.cpu() if smoke.tiny else mx.tpu()
    net, loss_fn = build_bert(cfg, ctx)
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 1e-4}, kvstore=None)
    step = TrainStep(net, loss_fn, trainer, mesh=None)
    ids, labels = bert_batch(cfg, cfg["batch"])
    feed = DeviceFeed([(ids, labels)] * cfg["steps"], ctx=ctx)
    losses = []
    try:
        with amp.scope("bfloat16"):
            for batch in feed:
                loss = step(batch)
                losses.append(float(loss.asscalar()))
                if len(losses) == 1:
                    after_first = smoke.compiles
        late_compiles = smoke.compiles - after_first
    finally:
        feed.close()
    check(len(losses) == cfg["steps"], "feed ended early: %r" % losses)
    check(all(np.isfinite(losses)), "non-finite loss: %r" % losses)
    check(losses[-1] < losses[0], "loss did not fall: %r" % losses)
    check(late_compiles == 0,
          "%d compile(s) after the first step" % late_compiles)
    check(on_platform(loss._data, platform), "loss not on %s" % platform)
    for p in net.collect_params().values():
        check(on_platform(p.data()._data, platform),
              "%s not on %s" % (p.name, platform))
    choice = kernels.choose("flash_attention", seq=cfg["seq"],
                            block_q=256, block_k=256)
    info = {"losses": [round(v, 4) for v in losses],
            "compiles_after_step_1": late_compiles,
            "flash_attention": {"use_pallas": choice.use_pallas,
                                "interpret": choice.interpret,
                                "reason": choice.reason}}
    if not smoke.tiny:
        check(choice.use_pallas and not choice.interpret,
              "flash attention not selected: %s" % choice.reason)
        check("tpu_custom_call" in compiled_text(step),
              "compiled BERT step holds no tpu_custom_call")
        info["tpu_custom_call"] = True
        in_use, limit = ctx.memory_info()
        check(limit > 0, "memory_info() reports no limit")
        info["memory_info"] = [in_use, limit]
    return info


def phase_train_resnet(smoke, cfg):
    import mxnet_tpu as mx
    from mxnet_tpu import amp, gluon
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.parallel import TrainStep

    ctx = mx.cpu() if smoke.tiny else mx.tpu()
    seed_everything(mx)
    if cfg["net"] == "resnet50_v1":
        net = vision.resnet50_v1()
    else:           # rehearsal: ResNet-50's block type, one per stage
        from mxnet_tpu.gluon.model_zoo.vision.resnet import (BottleneckV1,
                                                             ResNetV1)
        net = ResNetV1(BottleneckV1, [1, 1, 1, 1], [8, 16, 32, 64, 128],
                       classes=cfg["classes"])
    net.initialize(ctx=ctx)
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.05, "momentum": 0.9},
                            kvstore=None)
    step = TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(), trainer,
                     mesh=None)
    k, b, hw = cfg["k"], cfg["batch"], cfg["image"]
    x = mx.nd.random.normal(shape=(k, b, 3, hw, hw), ctx=ctx)
    y = mx.nd.random.randint(0, cfg["classes"], shape=(k, b),
                             ctx=ctx).astype("float32")
    with amp.scope("bfloat16"):
        losses = step.run_steps(x, y).asnumpy()
    check(losses.shape == (k,) and np.all(np.isfinite(losses)),
          "run_steps losses: %r" % (losses,))
    # a fresh running_mean is all zeros; k steps of batch statistics
    # must have moved it
    moved = [p.name for p in net.collect_params().values()
             if p.name.endswith("running_mean")
             and float(np.abs(p.data().asnumpy()).max()) > 0]
    check(moved, "no BatchNorm running_mean moved")
    return {"losses": [round(float(v), 4) for v in losses],
            "bn_running_means_moved": len(moved)}


def phase_train_bert_dp(smoke, cfg, dp):
    """The BERT trainer over a ``dp`` mesh, held to the one-chip loss
    on the same global batch."""
    import mxnet_tpu as mx
    from jax.sharding import NamedSharding, PartitionSpec as P
    from mxnet_tpu import amp, gluon
    from mxnet_tpu.analysis.sharding import collective_profile
    from mxnet_tpu.dataio import DeviceFeed
    from mxnet_tpu.parallel import TrainStep, make_mesh

    chips = dp["chips"]
    check(len(jax.devices()) >= chips,
          "--chips %d needs %d devices, found %d"
          % (chips, chips, len(jax.devices())))
    ctx = mx.cpu() if smoke.tiny else mx.tpu()
    net, loss_fn = build_bert(cfg, ctx)
    ids, labels = bert_batch(cfg, dp["batch"])

    # one-chip loss first, forward only, a quarter of the batch at a
    # time (the whole global batch does not fit one chip's training
    # step), before any update moves the weights
    part = dp["batch"] // chips
    with amp.scope("bfloat16"):
        one_chip = float(np.mean([
            loss_fn(net(mx.nd.array(ids[i:i + part], ctx=ctx)),
                    mx.nd.array(labels[i:i + part], ctx=ctx))
            .asnumpy().mean()
            for i in range(0, dp["batch"], part)]))

    mesh = make_mesh({"dp": chips})
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 1e-4}, kvstore=None)
    step = TrainStep(net, loss_fn, trainer, mesh=mesh)
    feed = DeviceFeed([(ids, labels)] * dp["steps"], mesh=mesh)
    losses = []
    try:
        with amp.scope("bfloat16"):
            for batch in feed:
                if not losses:
                    shard_devices = {s.device
                                     for s in batch.data._data
                                     .addressable_shards}
                losses.append(float(step(batch).asscalar()))
    finally:
        feed.close()
    check(len(shard_devices) == chips,
          "batch shards sit on %d device(s)" % len(shard_devices))
    check(all(np.isfinite(losses)) and losses[-1] < losses[0],
          "dp losses: %r" % losses)
    check(abs(losses[0] - one_chip) <= LOSS_TOL,
          "first dp loss %.4f vs one-chip loss %.4f"
          % (losses[0], one_chip))
    rep = NamedSharding(mesh, P())
    by_batch = NamedSharding(mesh, P("dp", None))
    profile = collective_profile(compiled_text(step,
                                               (by_batch, by_batch, rep)))
    check("all-reduce" in profile, "no all-reduce in the compiled step: "
          "%r" % profile)
    info = {"losses": [round(v, 4) for v in losses],
            "one_chip_loss": round(one_chip, 4),
            "shard_devices": sorted(str(d) for d in shard_devices),
            "collectives": profile}
    if not smoke.tiny:
        peaks = [d.memory_stats()["peak_bytes_in_use"]
                 for d in jax.devices()[:chips]]
        check(all(p > 0 for p in peaks), "idle device: %r" % peaks)
        info["peak_bytes_per_device"] = peaks
    return info


# ----------------------------------------------------------------------
# generative serving
# ----------------------------------------------------------------------

def phase_serve(smoke, cfg):
    import mxnet_tpu as mx
    from mxnet_tpu.serving.decode import TinyGPT

    model = TinyGPT(vocab_size=cfg["vocab"], units=cfg["units"],
                    num_layers=cfg["layers"], num_heads=cfg["heads"],
                    max_seq=cfg["max_seq"])
    params = model.init_params(seed=0)
    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, cfg["vocab"], n).tolist()
               for n in cfg["prompt_lens"]]
    max_new = cfg["max_new"]

    reg = mx.serving.ModelRegistry()
    try:
        servable = reg.register_generative("smoke_gpt", model,
                                           params=params,
                                           num_blocks=cfg["kv_blocks"])
        engine = servable.engine
        for kind, buckets in (("prefill", engine.prefill_buckets),
                              ("decode", engine.decode_buckets)):
            for b in buckets:
                check(engine.fingerprint(kind, b) is not None,
                      "%s bucket %d not warmed" % (kind, b))
                # in place: the program writes into its donated slabs
                mem = engine.program_memory(kind, b)
                check(mem is None or mem["aliased_bytes"]
                      == engine.cache.slab_bytes(),
                      "%s bucket %d copies its K/V slabs: %r of %d bytes "
                      "aliased" % (kind, b, mem, engine.cache.slab_bytes()))
        warmed = smoke.compiles
        streams = [reg.generate("smoke_gpt", p, max_new) for p in prompts]
        outs = [s.tokens() for s in streams]
        late_compiles = smoke.compiles - warmed
    finally:
        reg.shutdown(drain=True)
    check(all(len(o) == max_new for o in outs),
          "streams ended early: %r" % [len(o) for o in outs])
    check(late_compiles == 0,
          "%d compile(s) after warm-up" % late_compiles)

    # float32 reference: the full causal forward over each stream's
    # own tokens (teacher forced), padded to one length so it compiles
    # once -- causal attention makes the padding invisible
    width = max(cfg["prompt_lens"]) + max_new
    tokens = np.zeros((len(prompts), width), np.int32)
    for i, (p, o) in enumerate(zip(prompts, outs)):
        tokens[i, :len(p) + max_new] = p + o
    tokens = jnp.asarray(tokens)
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(jax.jit(model.full_logits)(params, tokens))
    chip = np.asarray(jax.jit(
        lambda p, t: model.full_logits(p, t))(params, tokens))
    gap = float(np.abs(chip - ref).max())
    check(gap <= LOGIT_TOL, "default-precision logits are %.3f from the "
          "float32 reference (tolerance %.2f)" % (gap, LOGIT_TOL))
    exact = 0
    for i, (p, o) in enumerate(zip(prompts, outs)):
        for t, tok in enumerate(o):
            row = ref[i, len(p) - 1 + t]
            # argmax of logits within LOGIT_TOL of the reference lies
            # within 2*LOGIT_TOL of the reference's maximum; where the
            # top-two margin is wider than that, it is the argmax
            check(row[tok] >= row.max() - 2 * LOGIT_TOL,
                  "stream %d token %d: logit %.3f, reference max %.3f"
                  % (i, t, row[tok], row.max()))
            exact += int(tok == int(row.argmax()))
    return {"streams": len(outs), "new_tokens": max_new,
            "prefill_buckets": list(engine.prefill_buckets),
            "decode_buckets": list(engine.decode_buckets),
            "compiles_after_warmup": late_compiles,
            "kv_slab_bytes": engine.cache.slab_bytes(),
            "logit_gap_default_vs_float32": round(gap, 4),
            "logit_tolerance": LOGIT_TOL,
            "tokens_equal_reference_argmax": "%d/%d"
            % (exact, len(outs) * max_new)}


# ----------------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: the data-parallel trainer over four chips")
    ap.add_argument("--tiny", action="store_true",
                    help="CPU rehearsal at toy sizes; ends in REHEARSAL")
    args = ap.parse_args(argv)

    t_start = time.perf_counter()
    backend = jax.default_backend()
    if args.tiny:
        if backend != "cpu":
            sys.exit("--tiny is the CPU rehearsal; run it under "
                     "JAX_PLATFORMS=cpu (backend is %r)" % backend)
    elif backend != "tpu":
        sys.exit("chip_smoke.py needs a TPU: jax.default_backend() is "
                 "%r.  --tiny rehearses the code on the CPU." % backend)

    import mxnet_tpu  # noqa: F401  (places the compile cache)
    from mxnet_tpu.profiling import roofline
    smoke = Smoke(args.tiny)
    if not args.tiny:
        # raises for a device kind the peaks table does not know
        roofline.device_peaks(smoke.stamp["device_kind"])
    cfg = TINY if args.tiny else FULL
    smoke.line(phase="start", chips=args.chips, **cache_state())

    if args.chips == 4:
        smoke.phase("train_bert_dp", phase_train_bert_dp, cfg["bert"],
                    cfg["dp"])
    else:
        smoke.phase("census", phase_census, cfg["census"])
        smoke.phase("train_bert", phase_train_bert, cfg["bert"])
        smoke.phase("train_resnet", phase_train_resnet, cfg["resnet"])
        smoke.phase("serve", phase_serve, cfg["gpt"])

    smoke.line(phase="end", wall_s=round(time.perf_counter() - t_start, 1),
               compiles=smoke.compiles, **smoke.cache_log.summary(),
               **cache_state())
    if args.tiny:
        print("REHEARSAL")
    else:
        print(json.dumps({"ok": True, "device": {
            "platform": smoke.stamp["platform"],
            "kind": smoke.stamp["device_kind"],
            "count": smoke.stamp["device_count"]}}), flush=True)


if __name__ == "__main__":
    main()
