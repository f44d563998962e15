"""Measure a training cell: steps of the program's compiled train step on
batches that come through its input staging, for a fixed time.

The loop is a logging user's: one ``step(batch)`` per batch from a
``DeviceFeed``, the loss fetched every few steps and at the end.  The
window opens after warm-up and is closed by the last loss fetch, which
waits for the device, so the rate is of completed steps.
"""
import itertools
import time

import numpy as np


def check_forward(run, built):
    """``correct``, part 1: the system's forward loss per token under its
    AMP policy against the float32 reference on the same weights and
    tokens, before any update."""
    fam, cfg = run.family, run.cfg
    chk = cfg["check"]
    ids, labels = fam.make_batches(cfg, chk["sequences"],
                                   run.args.seed + 1, 1)[0]
    got = fam.system_token_losses(built, ids, labels)
    want = fam.reference_token_losses(
        fam.reference_params(built.net), ids.astype(np.int64),
        labels.astype(np.int64), cfg)
    gap_mean = abs(float(got.mean()) - float(want.mean()))
    gap_token = float(np.abs(got - want).max())
    run.log.line(event="reference_check", tokens=int(got.size),
                 system_loss=float(got.mean()),
                 reference_loss=float(want.mean()),
                 gap_mean=gap_mean, gap_token_max=gap_token,
                 tol_mean=chk["tol_mean_loss"],
                 tol_token=chk["tol_token_loss"])
    run.compare("loss_gap_mean", gap_mean, chk["tol_mean_loss"],
                "forward loss (mean) from the float32 reference")
    run.compare("loss_gap_token", gap_token, chk["tol_token_loss"],
                "forward loss (worst token) from the float32 reference")


def run_cell(run, compile_log):
    from mxnet_tpu import amp
    from mxnet_tpu.dataio import DeviceFeed

    fam, cfg, mix = run.family, run.cfg, run.mix
    if mix["kind"] != "train_stream":
        raise ValueError("a training configuration takes a train_stream "
                         "mix, not %r" % mix["kind"])
    built = fam.build_model(cfg, run.args.seed, run.stamp["platform"])
    check_forward(run, built)
    fam.make_step(built, cfg, run.chips)

    global_batch = cfg["deployment"]["batch_per_chip"] * run.chips
    tokens_per_step = global_batch * cfg["seq_len"]
    ring = fam.make_batches(cfg, global_batch, run.args.seed, mix["ring"])
    place = {"mesh": built.mesh} if built.mesh is not None \
        else {"ctx": built.ctx}
    feed = DeviceFeed(itertools.cycle(ring), **place)
    every = int(mix["loss_fetch_every"])
    losses, steps = [], 0
    try:
        with amp.scope(fam.AMP_DTYPE):
            for _ in range(int(mix["warmup_steps"])):
                batch = next(feed)
                loss = built.step(batch)
            losses.append(float(loss.asscalar()))
            shard_devices = {s.device for s in
                             batch.data._data.addressable_shards}
            setup = compile_log.snapshot()
            wait0 = feed.stats()["consumer_wait"]
            secs = run.window_seconds()
            with run.traced_window():
                t0 = run.t_window_open
                while time.perf_counter() < t0 + secs:
                    with run.span("perfbench.feed_next"):
                        batch = next(feed)
                    with run.span("perfbench.step_dispatch"):
                        loss = built.step(batch)
                    steps += 1
                    if steps % every == 0:
                        with run.span("perfbench.loss_fetch"):
                            losses.append(float(loss.asscalar()))
                with run.span("perfbench.loss_fetch"):
                    losses.append(float(loss.asscalar()))
                t1 = time.perf_counter()
            wait1 = feed.stats()["consumer_wait"]
    finally:
        feed.close()

    run.window_s = t1 - t0
    run.attempted, run.failed = steps, 0
    after = compile_log.snapshot()
    in_window = after["requests"] - setup["requests"]
    run.compare("compiles_in_window", in_window, 0,
                "compiles inside the measured window")
    if not all(np.isfinite(losses)):
        run.incorrect("non-finite loss among %r" % (losses,))
    if len(shard_devices) != run.chips:
        run.incorrect("a batch sits on %d device(s), the cell has %d chips"
                      % (len(shard_devices), run.chips))
    rate = steps * tokens_per_step / run.window_s
    run.end_to_end["train_tokens_per_s"] = rate
    run.end_to_end["setup_s"] = run.setup_seconds(t0)
    run.counters.update(
        steps=steps, tokens_per_step=tokens_per_step,
        tokens_per_s=rate, batch_per_chip=global_batch // run.chips,
        feed_consumer_wait_s=wait1 - wait0,
        compile_requests_setup=setup["requests"],
        cache_hits_setup=setup["cache_hits"],
        compiles_in_window=in_window)
    run.log.measurement("window", kind="train", seconds=run.window_s,
                 steps=steps, tokens_per_step=tokens_per_step,
                 train_tokens_per_s=rate,
                 step_ms_host=1e3 * run.window_s / max(steps, 1),
                 first_loss=losses[0], last_loss=losses[-1],
                 losses_fetched=len(losses),
                 feed_consumer_wait_s=wait1 - wait0,
                 compile_requests_setup=setup["requests"],
                 cache_hits_setup=setup["cache_hits"],
                 compiles_in_window=in_window,
                 setup_s=run.end_to_end["setup_s"])


IDLE_DEFAULT = "host, outside the benchmark's spans"


def extra_spans(run):
    return ()
