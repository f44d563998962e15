"""Multi-process initialization (reference: the ps-lite bootstrap in
``src/kvstore/kvstore_dist.h`` + ``tools/launch.py`` env protocol).

One call wires a worker into the ``jax.distributed`` world using the
environment set by ``tools/launch.py``; after it, ``jax.devices()``
spans every host's chips and the dist kvstore / sharded train steps
reduce over ICI/DCN collectives.

Failure model (ISSUE 15, docs/distributed.md): every cross-process
wait in this module -- collective sends/receives and barriers -- is
*attributed*.  A dead or wedged peer never surfaces as a raw jaxlib
``DEADLINE_EXCEEDED``; it surfaces as :class:`BarrierTimeout` /
:class:`RankFailure` carrying the barrier tag, the sequence number,
the missing rank(s) (cross-checked against each rank's liveness lease
key, beaten from the training loop), and the elapsed wait.  Transient
coordination-KV errors -- and only those -- retry with bounded
backoff.  All coordination keys are namespaced by the supervisor
*generation* id (``MXNET_TPU_GENERATION``), so an elastic restart
starts clean and sweeps the dead generation's keys.
"""
from __future__ import annotations

import os
import time

from . import chaos as _chaos
from .base import MXNetError

_initialized = False


class RankFailure(MXNetError):
    """A cross-process operation gave up on one or more peer ranks.

    Carries ``tag`` (the barrier/collective name), ``seq`` (the
    lockstep sequence number), ``ranks`` (the peers attributed --
    missing, aborted, or unreachable), and ``elapsed_s``.
    """

    def __init__(self, msg, tag=None, seq=None, ranks=(), elapsed_s=None):
        super().__init__(msg)
        self.tag = tag
        self.seq = seq
        self.ranks = tuple(ranks)
        self.elapsed_s = elapsed_s


class BarrierTimeout(RankFailure):
    """A barrier rendezvous timed out; ``ranks`` names every rank that
    never acked (``presumed_dead`` the subset whose liveness lease is
    stale or absent)."""

    def __init__(self, msg, tag=None, seq=None, ranks=(), elapsed_s=None,
                 presumed_dead=()):
        super().__init__(msg, tag=tag, seq=seq, ranks=ranks,
                         elapsed_s=elapsed_s)
        self.presumed_dead = tuple(presumed_dead)


class _KVTimeout(Exception):
    """Internal: a blocking KV get hit its deadline.  Callers convert
    it into the typed error that names what they were waiting for."""

    def __init__(self, elapsed_s):
        super().__init__("%.3fs" % elapsed_s)
        self.elapsed_s = elapsed_s


def distributed_init(coordinator_address=None, num_processes=None,
                     process_id=None):
    """Initialize the multi-process JAX runtime from arguments or the
    launcher's environment (MXNET_TPU_COORDINATOR / _NUM_PROCS /
    _PROC_ID).  No-op when single-process or already initialized."""
    global _initialized
    if _initialized:
        return False
    coordinator_address = coordinator_address or \
        os.environ.get("MXNET_TPU_COORDINATOR")
    num_processes = num_processes if num_processes is not None else \
        int(os.environ.get("MXNET_TPU_NUM_PROCS", "1"))
    process_id = process_id if process_id is not None else \
        int(os.environ.get("MXNET_TPU_PROC_ID", "0"))
    if coordinator_address is None or num_processes <= 1:
        return False
    import jax
    # CPU backends need a cross-process collectives implementation to
    # join a multi-process world (TPU uses ICI natively)
    try:
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    except Exception:
        pass
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id)
    _initialized = True
    return True


# ----------------------------------------------------------------------
# Host-side collectives.
#
# On TPU pods the backend is multi-process and XLA collectives ride
# ICI/DCN (use those inside jit).  On CPU, ``distributed_init`` wires
# gloo collectives BEFORE backend creation, so the backend world is
# multi-process there too and ``host_allreduce``/``host_broadcast``
# take the same ``process_allgather`` path a pod takes -- exercised
# in-suite by tests/test_distributed.py::
# test_two_process_backend_collectives_gloo.  Only when the backend
# failed to come up multi-process (a jaxlib without gloo, or a backend
# initialized before distributed_init) does the coordination service's
# key-value store carry the reduction -- structurally the reference's
# ps-lite server path: workers push values, every worker pulls and
# reduces.  That fallback funnels O(N*P) bytes through the coordinator
# and warns once (_warn_kv_fallback); it is a test-environment escape
# hatch, never the pod path.
# ----------------------------------------------------------------------

_seq = [0]
_my_old_keys = []   # this rank's keys from past rounds, deleted lazily


def generation():
    """The supervisor generation this process belongs to
    (``MXNET_TPU_GENERATION``, bumped by the elastic restart
    supervisor on every relaunch).  Namespaces every coordination-KV
    key, so a restarted world never reads the dead world's state."""
    try:
        return int(os.environ.get("MXNET_TPU_GENERATION", "0") or 0)
    except ValueError:
        return 0


def _kv_set(client, key, data):
    if hasattr(client, "key_value_set_bytes"):
        client.key_value_set_bytes(key, data)
    else:
        import base64
        client.key_value_set(key, base64.b64encode(data).decode())


def _kv_get(client, key, timeout_ms):
    if hasattr(client, "blocking_key_value_get_bytes"):
        return client.blocking_key_value_get_bytes(key, timeout_ms)
    import base64
    return base64.b64decode(client.blocking_key_value_get(key,
                                                          timeout_ms))


def _is_deadline(exc):
    return "DEADLINE_EXCEEDED" in str(exc)


def _kv_attempt(fn, what, kind, seq):
    """One coordination-KV op under the ``dist.collective`` fail point
    with bounded retry: transient errors (and chaos-injected RAISEs --
    the fail point sits INSIDE the retry domain, so an injected fault
    is tolerated the way real weather is) retry up to
    ``MXNET_TPU_DIST_KV_RETRIES`` times with doubling backoff, each
    tolerated one counted ``chaos.survived('dist.collective')``.  A
    deadline is NOT transient -- it means a peer never produced the
    value -- and converts immediately to :class:`_KVTimeout` for the
    caller to attribute."""
    from . import env as _env
    retries = int(_env.get("MXNET_TPU_DIST_KV_RETRIES"))
    delay = 0.05
    t0 = time.monotonic()
    for attempt in range(retries + 1):
        try:
            # chaos: the host-collective send/recv path -- a RAISE here
            # models a flaky coordination service and must be absorbed
            # by this bounded retry; a KILL is a rank dying mid-exchange
            _chaos.fail_point("dist.collective", what=what, kind=kind,
                              seq=seq, attempt=attempt + 1)
            return fn()
        except _KVTimeout:
            raise
        except Exception as e:
            if _is_deadline(e):
                raise _KVTimeout(time.monotonic() - t0) from e
            if attempt >= retries:
                raise RankFailure(
                    "coordination KV %s %r failed after %d attempt(s): "
                    "%s" % (what, kind, attempt + 1, e),
                    tag=kind, seq=seq,
                    elapsed_s=time.monotonic() - t0) from e
            _chaos.survived("dist.collective", "kv_retry")
            time.sleep(delay)
            delay *= 2


def _kv_set_checked(client, key, data, kind, seq):
    return _kv_attempt(lambda: _kv_set(client, key, data),
                       "set:" + key, kind, seq)


def _kv_get_checked(client, key, timeout_ms, kind, seq):
    return _kv_attempt(lambda: _kv_get(client, key, timeout_ms),
                       "get:" + key, kind, seq)


_PREV_GEN_SWEPT = [False]


def _sweep_previous_generation(client, rank):
    """Once per process (rank 0 only): delete the PREVIOUS supervisor
    generation's coordination keys.  A long-lived coordination service
    (a TPU pod's) carries the dead world's barrier acks, collective
    payloads, and liveness leases across an elastic restart; the new
    generation's first rendezvous sweeps them so stale acks can never
    satisfy a new barrier.  The trailing ``/`` makes each delete a
    recursive directory delete in the coordination service."""
    if _PREV_GEN_SWEPT[0] or rank != 0:
        return
    _PREV_GEN_SWEPT[0] = True
    gen = generation()
    if gen <= 0:
        return
    for prefix in ("mxbar", "mxlive", "mxkv_ar", "mxkv_bc"):
        try:
            client.key_value_delete("%s/g%d/" % (prefix, gen - 1))
        except Exception:
            pass


def _gc_old_keys(client):
    """Delete this rank's keys from two rounds back.  Collectives are
    lockstep on _seq: entering round N+1 implies every rank has POSTED
    round N, hence fully consumed round N-1 -- deleting N-1 entries is
    race-free, and the coordinator store stays bounded.  Also sweeps a
    previous supervisor generation's keys once (see
    :func:`_sweep_previous_generation`)."""
    _sweep_previous_generation(client, world()[1])
    while len(_my_old_keys) > 1:
        key = _my_old_keys.pop(0)
        try:
            client.key_value_delete(key)
        except Exception:
            pass


def world():
    """(num_processes, process_id) of the connected world (1, 0 when
    single-process)."""
    from jax._src import distributed
    gs = distributed.global_state
    if gs.client is None:
        return 1, 0
    return gs.num_processes, gs.process_id


def _client():
    from jax._src import distributed
    return distributed.global_state.client


# ----------------------------------------------------------------------
# Liveness leases.
#
# Attribution needs a second signal besides "no barrier ack": a rank
# that is merely slow still BEATS its lease (the training loop beats it
# every step, and every barrier entry refreshes it), while a dead rank
# stops.  A missing rank whose lease is stale past
# MXNET_TPU_DIST_LEASE_TTL_S (or absent) is *presumed dead* in the
# typed error -- the operator-facing difference between "preempted
# host" and "straggler".  Lease keys live in the coordination KV store
# under the current generation (``mxlive/g<gen>/<rank>``).
# ----------------------------------------------------------------------

def _lease_key(rank):
    return "mxlive/g%d/%d" % (generation(), rank)


def beat_lease():
    """Refresh this rank's liveness lease (no-op single-process).
    Called from the training loop (``ContinuousTrainer``) and at every
    barrier entry; the value is this host's wall clock, compared only
    for staleness (single-digit-seconds skew is harmless against the
    default 10 s TTL)."""
    nproc, rank = world()
    if nproc == 1:
        return False
    try:
        _kv_set(_client(), _lease_key(rank), repr(time.time()).encode())
    except Exception:
        return False            # a failed beat must never kill a step
    return True


def lease_beater():
    """A bound zero-arg beater when this process is part of a
    multi-process world, else ``None`` -- so hot loops pay one
    attribute check per step, never a ``world()`` probe (the
    zero-overhead contract tests/test_resilience.py proves)."""
    return beat_lease if world()[0] > 1 else None


def lease_age(rank, timeout_ms=200):
    """Seconds since ``rank`` last beat its lease, or ``None`` when it
    never has (or the probe timed out)."""
    try:
        raw = _kv_get(_client(), _lease_key(rank), timeout_ms)
        return max(0.0, time.time() - float(raw.decode()))
    except Exception:
        return None


def stale_ranks(ttl_s=None, ranks=None):
    """Ranks whose lease is absent or older than ``ttl_s``
    (``MXNET_TPU_DIST_LEASE_TTL_S``) -- the presumed-dead set."""
    from . import env as _env
    if ttl_s is None:
        ttl_s = float(_env.get("MXNET_TPU_DIST_LEASE_TTL_S"))
    nproc, _rank = world()
    out = []
    for r in range(nproc) if ranks is None else ranks:
        age = lease_age(r)
        if age is None or age > ttl_s:
            out.append(r)
    return out


def _telemetry_rank_failure(kind, tag, ranks, elapsed_s):
    from . import telemetry as _telemetry
    if _telemetry._ENABLED:
        _telemetry.hooks.dist_rank_failure(kind, tag, ranks, elapsed_s)


_KV_FALLBACK_WARNED = [False]


def _warn_kv_fallback():
    """The coordination-service KV transport funnels every rank's full
    tensor through the coordinator: O(N*P) bytes through one process.
    It exists for test environments whose backend world is
    single-process (jax.process_count() == 1 despite a multi-rank
    launcher).  A MULTI-process backend reaching this path means the
    world sizes disagree -- a misconfigured pod where backend
    collectives should have run -- so that case errors instead of
    silently funneling a pod's gradients through one host."""
    import warnings
    import jax
    if jax.process_count() > 1:
        from .base import MXNetError
        raise MXNetError(
            "host collective fallback (coordination-service KV) reached "
            "with a multi-process backend (jax.process_count()=%d != "
            "launcher world): the distributed init is misconfigured; "
            "backend collectives must run on a pod (check "
            "tools/launch.py / JAX distributed init)"
            % jax.process_count())
    if not _KV_FALLBACK_WARNED[0]:
        _KV_FALLBACK_WARNED[0] = True
        warnings.warn(
            "using the coordination-service KV fallback for host "
            "collectives (backend is not multi-process); fine for "
            "tests, never the real-pod path")


def _result_device(arr):
    """Placement the collective's result should land on: the INPUT's
    sharding when it is a jax array (a Sharding is a valid device_put
    target, so mesh-sharded/replicated inputs come back with their
    layout instead of collapsing onto one device).  ``jnp.asarray``
    would place the result on the DEFAULT device instead, which need
    not be where the input lives -- every later use would then pay a
    cross-device copy."""
    import jax
    if isinstance(arr, jax.Array):
        return arr.sharding
    return None


def _place(x, placement):
    import jax
    import jax.numpy as jnp
    import numpy as np
    if placement is None:
        return jnp.asarray(x)
    if isinstance(placement, jax.sharding.Sharding) \
            and not placement.is_fully_addressable:
        # a multi-host sharding (the global mesh of docs/distributed.md)
        # cannot be device_put from host data; build the global array
        # from this process's addressable shards instead -- valid here
        # because collective results are identical on every rank
        x = np.asarray(x)
        return jax.make_array_from_callback(x.shape, placement,
                                            lambda idx: x[idx])
    return jax.device_put(x, placement)


def _telemetry_collective(kind, nbytes, ntensors):
    from . import telemetry as _telemetry
    if _telemetry._ENABLED:
        _telemetry.hooks.dist_collective(kind, nbytes, ntensors)


def host_allreduce(arr, average=False, timeout_ms=60000, _ntensors=1):
    """Sum (or mean) a host array across every process.  Uses backend
    collectives when the backend is multi-process; otherwise the
    coordination-service KV store.  The result lands on the input's
    device (see ``_result_device``).

    NOT a training-hot-path primitive: the compiled SPMD train step
    reduces gradients in-graph (GSPMD ``all-reduce`` over the global
    mesh, docs/distributed.md); this host collective survives for
    init-time broadcast and metric/overflow reduction only, and those
    call sites coalesce tensors through the ``*_bucketed`` wrappers."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    dev = _result_device(arr)
    nproc, rank = world()
    if nproc == 1:
        return _place(arr, dev)
    _telemetry_collective("allreduce", _nbytes_of(arr), _ntensors)
    if jax.process_count() == nproc:
        # chaos: the pod-shaped transport (gloo/ICI backend collective)
        _chaos.fail_point("dist.collective", what="allgather",
                          kind="allreduce", seq=_seq[0])
        from jax.experimental import multihost_utils
        try:
            g = multihost_utils.process_allgather(jnp.asarray(arr))
        except RankFailure:
            raise
        except Exception as e:
            elapsed = None
            dead = stale_ranks()
            _telemetry_rank_failure("collective", "allreduce", dead,
                                    elapsed)
            raise RankFailure(
                "backend allgather failed: %s%s"
                % (e, "; presumed dead rank(s): %s" % dead if dead
                   else ""),
                tag="allreduce", ranks=dead) from e
        out = jnp.mean(g, axis=0) if average else jnp.sum(g, axis=0)
        return _place(out, dev)
    _warn_kv_fallback()
    client = _client()
    x = np.asarray(arr)
    _seq[0] += 1
    seq = _seq[0]
    tag = "mxkv_ar/g%d/%d" % (generation(), seq)
    my_key = "%s/%d" % (tag, rank)
    _kv_set_checked(client, my_key, x.tobytes(), "allreduce", seq)
    total = np.zeros_like(x)
    t0 = time.monotonic()
    for r in range(nproc):
        try:
            raw = _kv_get_checked(client, "%s/%d" % (tag, r),
                                  timeout_ms, "allreduce", seq)
        except _KVTimeout as e:
            dead = stale_ranks(ranks=[r])
            _telemetry_rank_failure("collective", "allreduce", [r],
                                    e.elapsed_s)
            raise RankFailure(
                "allreduce (seq %d) timed out after %.1fs waiting for "
                "rank %d's value%s" % (
                    seq, time.monotonic() - t0, r,
                    " (presumed dead: lease stale/absent)" if dead
                    else ""),
                tag="allreduce", seq=seq, ranks=[r],
                elapsed_s=time.monotonic() - t0) from e
        total += np.frombuffer(raw, dtype=x.dtype).reshape(x.shape)
    _my_old_keys.append(my_key)
    _gc_old_keys(client)
    if average:
        total = total / nproc
    return _place(total, dev)


def host_broadcast(arr, root=0, timeout_ms=60000, _ntensors=1):
    """Every process receives root's value (placed on the input's
    device, see ``_result_device``).  Init-time parameter sync only on
    the SPMD path -- see ``host_allreduce``'s note."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    dev = _result_device(arr)
    nproc, rank = world()
    if nproc == 1:
        return _place(arr, dev)
    _telemetry_collective("broadcast", _nbytes_of(arr), _ntensors)
    if jax.process_count() == nproc:
        # chaos: the pod-shaped transport (gloo/ICI backend collective)
        _chaos.fail_point("dist.collective", what="broadcast",
                          kind="broadcast", seq=_seq[0])
        from jax.experimental import multihost_utils
        out = multihost_utils.broadcast_one_to_all(
            jnp.asarray(arr), is_source=(rank == root))
        return _place(out, dev)
    _warn_kv_fallback()
    client = _client()
    x = np.asarray(arr)
    _seq[0] += 1
    seq = _seq[0]
    tag = "mxkv_bc/g%d/%d" % (generation(), seq)
    if rank == root:
        _kv_set_checked(client, tag, x.tobytes(), "broadcast", seq)
        out = x
    else:
        try:
            raw = _kv_get_checked(client, tag, timeout_ms,
                                  "broadcast", seq)
        except _KVTimeout as e:
            dead = stale_ranks(ranks=[root])
            _telemetry_rank_failure("collective", "broadcast", [root],
                                    e.elapsed_s)
            raise RankFailure(
                "broadcast (seq %d) timed out after %.1fs waiting for "
                "root rank %d%s" % (
                    seq, e.elapsed_s, root,
                    " (presumed dead: lease stale/absent)" if dead
                    else ""),
                tag="broadcast", seq=seq, ranks=[root],
                elapsed_s=e.elapsed_s) from e
        out = np.frombuffer(raw, dtype=x.dtype).reshape(x.shape)
    # broadcast has no natural lockstep (root does not read), so an
    # attributed rendezvous gates the delete: after it, every rank has
    # consumed the key
    _wait_ranks("mxkv_bc_done", seq, nproc, rank, timeout_ms)
    if rank == root:
        try:
            client.key_value_delete(tag)
        except Exception:
            pass
    return _place(out, dev)


def failfast_exit(code=3):
    """Exit NOW, skipping the jax distributed client's shutdown
    barrier.  A survivor holding a typed :class:`RankFailure` cannot
    shut down cleanly: the coordination client's destructor waits at a
    shutdown barrier the dead rank will never join and LOG(FATAL)s the
    interpreter (SIGABRT) mid-teardown, burying the attributed error
    under coordination-service noise.  This flushes stdio and the
    telemetry sinks, then ``os._exit(code)`` -- the supervised-worker
    exit the elastic restart supervisor relaunches on (any nonzero
    exit triggers the relaunch; this one keeps the log and the exit
    code honest)."""
    import sys
    try:
        from . import telemetry as _telemetry
        if _telemetry._ENABLED:
            _telemetry.flush()
    except Exception:
        pass
    try:
        sys.stdout.flush()
        sys.stderr.flush()
    except Exception:
        pass
    os._exit(code)


def barrier(name="mxnet_tpu_barrier", timeout_ms=None):
    """Attributed rendezvous: every rank posts an ack key and waits for
    every other rank's, so a timeout NAMES the missing rank(s) in a
    typed :class:`BarrierTimeout` (never a raw jaxlib
    ``DEADLINE_EXCEEDED`` -- the pre-ISSUE-15 behavior was a 60 s hang
    followed by an unattributed KV exception on every survivor).
    ``timeout_ms`` defaults to ``MXNET_TPU_DIST_BARRIER_TIMEOUT_MS``.
    A rank that posted an *abort* ack (:func:`post_abort`) raises
    :class:`RankFailure` on every waiter instead -- the fast path a
    failing-but-alive peer takes so survivors never wait out the
    bound."""
    nproc, rank = world()
    if nproc == 1:
        return
    _seq[0] += 1
    _wait_ranks(name, _seq[0], nproc, rank, timeout_ms)


def post_abort(name, reason=""):
    """Mark the NEXT rendezvous at ``name`` aborted, so peers waiting
    there fail fast with a typed :class:`RankFailure` instead of
    waiting out the barrier bound.  Called by a rank that cannot
    complete a multi-rank protocol (e.g. a failed shard write inside
    ``save_sharded``); consumes the same lockstep seq the skipped
    barrier would have, so an aborting world stays seq-aligned."""
    nproc, rank = world()
    if nproc == 1:
        return
    _seq[0] += 1
    key = "mxbar/g%d/%s/%d/%d" % (generation(), name, _seq[0], rank)
    try:
        _kv_set(_client(), key,
                b"abort:" + reason.encode("utf-8", "replace"))
    except Exception:
        pass                    # peers then attribute via the timeout


def _wait_ranks(name, seq, nproc, rank, timeout_ms):
    """The rendezvous body shared by :func:`barrier` and the broadcast
    consumption gate: post ``mxbar/g<gen>/<name>/<seq>/<rank>``, then
    collect every peer's ack within the deadline."""
    from . import env as _env
    if timeout_ms is None:
        timeout_ms = int(_env.get("MXNET_TPU_DIST_BARRIER_TIMEOUT_MS"))
    client = _client()
    beat_lease()                # rendezvousing is proof of life
    base = "mxbar/g%d/%s/%d" % (generation(), name, seq)
    my_key = "%s/%d" % (base, rank)
    t0 = time.monotonic()
    _kv_set_checked(client, my_key, b"ok", name, seq)
    deadline = t0 + timeout_ms / 1000.0
    missing, aborted = [], []
    for r in range(nproc):
        if r == rank:
            continue
        remaining_ms = max(1, int(1000 * (deadline - time.monotonic())))
        try:
            val = _kv_get_checked(client, "%s/%d" % (base, r),
                                  remaining_ms, name, seq)
        except _KVTimeout:
            missing.append(r)
            # the deadline is spent; probe the remaining ranks with a
            # short grace each so the error names EVERY missing rank
            deadline = time.monotonic() + 0.2
            continue
        if val.startswith(b"abort"):
            aborted.append(r)
    _my_old_keys.append(my_key)
    _gc_old_keys(client)
    elapsed = time.monotonic() - t0
    if missing:
        dead = stale_ranks(ranks=missing)
        _telemetry_rank_failure("barrier", name, missing, elapsed)
        raise BarrierTimeout(
            "barrier %r (seq %d) timed out after %.1fs waiting for "
            "rank(s) %s%s" % (
                name, seq, elapsed, missing,
                "; presumed dead (liveness lease stale/absent): %s"
                % dead if dead else "; leases fresh (slow peer?)"),
            tag=name, seq=seq, ranks=missing, elapsed_s=elapsed,
            presumed_dead=dead)
    if aborted:
        _telemetry_rank_failure("abort", name, aborted, elapsed)
        raise RankFailure(
            "rank(s) %s aborted at barrier %r (seq %d) after %.1fs"
            % (aborted, name, seq, elapsed),
            tag=name, seq=seq, ranks=aborted, elapsed_s=elapsed)


def _nbytes_of(arr):
    try:
        import numpy as np
        shape = getattr(arr, "shape", ())
        dtype = getattr(arr, "dtype", None)
        if dtype is None:
            return 0
        n = 1
        for d in shape:
            n *= int(d)
        return n * np.dtype(dtype).itemsize
    except Exception:
        return 0


# ----------------------------------------------------------------------
# Bucketed host collectives.
#
# The surviving host-collective call sites (init-time parameter
# broadcast, metric/overflow reduction, the legacy eager kvstore path)
# used to issue ONE RPC PER TENSOR -- for an N-layer model that is N
# coordinator round-trips before the first step.  These wrappers
# flatten a whole list of tensors into one contiguous buffer per dtype
# and make ONE collective per buffer, then split results back onto each
# input's original placement.  ``dist.collectives`` vs
# ``dist.tensors_coalesced`` telemetry records the drop.
# ----------------------------------------------------------------------

def _as_host(x):
    """Host numpy view of one collective operand (NDArray / jax.Array /
    numpy).  Multi-host global arrays must be fully replicated -- which
    every replicated-parameter caller satisfies."""
    import numpy as np
    data = getattr(x, "_data", x)       # NDArray -> jax array
    return np.asarray(data)


def _bucketed(arrays, one_collective):
    """Flatten/concat/split machinery: group ``arrays`` by dtype, run
    ``one_collective(buffer, ntensors)`` once per group, and return the
    per-input results placed back on each input's sharding.  The
    grouping itself is the shared ``mxnet_tpu.bucketing`` helper -- the
    same logic the fused bucket-flattened optimizer update compiles
    over traced buffers (docs/kernels.md)."""
    import numpy as np
    from .bucketing import dtype_groups, flatten_group, split_group
    arrays = list(arrays)
    if not arrays:
        return []
    placements = [_result_device(getattr(a, "_data", a)) for a in arrays]
    hosts = [_as_host(a) for a in arrays]
    out = [None] * len(arrays)
    for _dtype, idxs in dtype_groups(hosts):
        buf = flatten_group(hosts, idxs, np)
        res = np.asarray(one_collective(buf, len(idxs)))
        pieces = split_group(res, [hosts[i].shape for i in idxs])
        for i, piece in zip(idxs, pieces):
            out[i] = _place(piece, placements[i])
    return out


def host_allreduce_bucketed(arrays, average=False, timeout_ms=60000):
    """Sum (or mean) a LIST of host arrays across every process with
    one flattened collective per dtype group instead of one RPC per
    tensor.  Results come back in input order, each on its input's
    placement."""
    nproc, _rank = world()
    if nproc == 1:
        return [_place(_as_host(a),
                       _result_device(getattr(a, "_data", a)))
                for a in arrays]
    return _bucketed(
        arrays,
        lambda buf, n: host_allreduce(buf, average=average,
                                      timeout_ms=timeout_ms,
                                      _ntensors=n))


def host_broadcast_bucketed(arrays, root=0, timeout_ms=60000):
    """Every process receives root's values for a LIST of arrays, one
    flattened collective per dtype group (the init-time parameter-sync
    path of docs/distributed.md)."""
    nproc, _rank = world()
    if nproc == 1:
        return [_place(_as_host(a),
                       _result_device(getattr(a, "_data", a)))
                for a in arrays]
    return _bucketed(
        arrays,
        lambda buf, n: host_broadcast(buf, root=root,
                                      timeout_ms=timeout_ms,
                                      _ntensors=n))
