"""Device mesh + data-parallel sharding — the TPU replacement for the
reference's MPI+NCCL distributed backend.

Reference wire protocol (SURVEY §5.8; src/caffe/parallel.cpp, clusters.cpp):
mpirun launches one process per node; rank 0 MPI_Bcasts a ncclUniqueId; a
global NCCL communicator allreduces gradient buckets on a dedicated stream,
overlapped with backward by a reduce thread; weights ncclBcast from rank 0
at start.

TPU-native equivalent implemented here:
- `Clusters` -> `init_distributed()` = jax.distributed.initialize (DCN),
  after which every host sees the global device list.
- ncclUniqueId handshake -> nothing: the TPU runtime already forms the
  ICI/DCN topology.
- per-GPU P2PSync threads -> SPMD: ONE jitted program over a
  jax.sharding.Mesh; XLA partitions it across all chips.
- weight broadcast -> replicated NamedSharding on params (device_put once).
- bucketed ncclAllReduce + reduce thread -> XLA inserts all-reduces for the
  gradient mean when the batch axis is sharded and params are replicated;
  its latency-hiding scheduler overlaps them with remaining backward
  compute, which is exactly the reference's reduce-thread/bucket overlap
  machinery (net.cpp:757-913) done by the compiler.
- divide_batch_size (parallel.cpp:295-348) -> the global batch is sharded
  over the 'data' axis; each chip sees batch/n_data examples.

The mesh also carries a 'model' axis so later tensor/pipeline-parallel
shardings slot in without changing this module's API.
"""

from __future__ import annotations

import logging
from dataclasses import dataclass

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

log = logging.getLogger("caffe_mpi_tpu.parallel")


def mark_varying(x, axis_name: str | None = None, *, like=None):
    """Mark a value as varying over mesh axes (shard_map's per-device
    type tracking) — the single definition used by ring attention and
    the pipeline schedule. Idempotent: axes x already varies over are
    skipped.

    like: instead of naming an axis, copy the varying-axis set of another
    value — scan carries built from jnp.zeros/full must match the vma of
    the sharded inputs they merge with, whatever axes the enclosing
    shard_map spans (e.g. 'data' x 'model' in a DPxSP step)."""
    from jax import lax
    axes = tuple(jax.typeof(like).vma) if like is not None else (axis_name,)
    have = jax.typeof(x).vma
    missing = tuple(a for a in axes if a and a not in have)
    return lax.pcast(x, missing, to="varying") if missing else x


def resolve_cluster(sp=None, host_id: int | None = None):
    """Resolve the elastic-cluster shape (ISSUE 11) from the solver
    knobs (`hosts` / `coordinator`) with env fallbacks
    (`CAFFE_TPU_NUM_HOSTS` / `CAFFE_TPU_COORDINATOR` /
    `CAFFE_TPU_HOST_ID`) — the reference reads the same facts from
    mpirun's environment (clusters.cpp:8-45). Returns
    (world, coordinator, rank); world <= 1 means single-host (the
    other two are then unchecked). An incomplete multi-host config
    raises resilience.ClusterError — a bounded, journalable failure
    instead of a later hang."""
    import os

    from ..utils import resilience
    world = int(getattr(sp, "hosts", 0) or 0) if sp is not None else 0
    if world <= 0:
        world = int(os.environ.get("CAFFE_TPU_NUM_HOSTS", "0") or 0)
    coordinator = (str(getattr(sp, "coordinator", "") or "")
                   if sp is not None else "")
    if not coordinator:
        coordinator = os.environ.get("CAFFE_TPU_COORDINATOR", "")
    rank = host_id if host_id is not None and host_id >= 0 else int(
        os.environ.get("CAFFE_TPU_HOST_ID", "-1") or -1)
    if world > 1:
        if not coordinator:
            raise resilience.ClusterError(
                f"hosts={world} but no coordinator: set the solver "
                "`coordinator` knob, -coordinator, or "
                "CAFFE_TPU_COORDINATOR")
        if not 0 <= rank < world:
            raise resilience.ClusterError(
                f"hosts={world} needs a host id in [0, {world}): set "
                "-host_id or CAFFE_TPU_HOST_ID")
    return world, coordinator, rank


def init_distributed(coordinator: str | None = None,
                     num_processes: int | None = None,
                     process_id: int | None = None, *,
                     attempts: int = 3, base_delay: float = 1.0,
                     timeout_s: float | None = None) -> None:
    """Multi-host init (reference Clusters::Init / MPI_Init,
    clusters.cpp:8-12). On single-host this is a no-op; under a
    multi-host launcher either the TPU runtime autodetects or the
    caller passes coordinator/num_processes/process_id explicitly.

    Hardened (ISSUE 11): each attempt is bounded by
    `initialization_timeout` (default from CAFFE_TPU_INIT_TIMEOUT, 60 s
    — the in-library connect loop already retries until then, so one
    attempt absorbs a coordinator that is merely *restarting*), failed
    attempts back off exponentially, and exhaustion raises
    resilience.ClusterError — a missing coordinator is a bounded,
    journaled exit-87 failure, never a hang. The `coordinator_down`
    fault site fails the first `count` attempts for the recovery
    suite."""
    if num_processes is None or num_processes <= 1:
        return
    import os
    import time

    from ..utils import resilience
    from ..utils.resilience import FAULTS
    if timeout_s is None:
        timeout_s = float(os.environ.get("CAFFE_TPU_INIT_TIMEOUT", "60")
                          or 60)
    delay = base_delay
    last: Exception | None = None
    for attempt in range(max(attempts, 1)):
        try:
            FAULTS.maybe_raise(
                "coordinator_down", RuntimeError,
                f"injected coordinator outage (attempt {attempt + 1})")
            jax.distributed.initialize(
                coordinator_address=coordinator,
                num_processes=num_processes, process_id=process_id,
                initialization_timeout=int(max(timeout_s, 1)))
            log.info("jax.distributed initialized: process %d/%d "
                     "(coordinator %s, attempt %d)", jax.process_index(),
                     jax.process_count(), coordinator, attempt + 1)
            return
        except Exception as e:  # noqa: BLE001 — every failure class
            # (gRPC unavailable, timeout, duplicate registration
            # against a dying coordinator) retries the same way
            last = e
            try:
                jax.distributed.shutdown()
            # lint: ok(typed-failure) — partial-init teardown; the
            # retry loop re-raises the real failure as ClusterError
            except Exception:  # noqa: BLE001 — partial init state
                pass
            if attempt + 1 >= max(attempts, 1):
                break
            log.warning("distributed init attempt %d/%d failed (%s); "
                        "retrying in %.1fs", attempt + 1, attempts, e,
                        delay)
            time.sleep(delay)
            delay = min(delay * 2, 30.0)
    raise resilience.ClusterError(
        f"distributed init failed after {attempts} attempt(s) against "
        f"coordinator {coordinator!r}: {last}") from last


def shutdown_distributed() -> None:
    """Best-effort jax.distributed teardown (after the exit barrier):
    rank 0's coordination service must not die underneath a peer that
    is still mid-KV-call."""
    try:
        jax.distributed.shutdown()
    # lint: ok(typed-failure) — already down IS the goal state; there
    # is nothing left to type or journal after the exit barrier
    except Exception:  # noqa: BLE001 — already down is fine
        pass


def _cluster_client():
    """The live coordination-service client, or None outside a
    jax.distributed run. jax exposes it only through the private
    global_state (jax.distributed has initialize/is_initialized/
    shutdown and no client accessor)."""
    from jax._src import distributed
    return distributed.global_state.client


def cluster_barrier(name: str, timeout_s: float = 600.0) -> bool:
    """All-hosts sync point on the coordination service (snapshot
    commit, end-of-training). True on success; False on timeout or a
    dead service — callers map False to a journaled EXIT_CLUSTER, the
    bounded alternative to waiting forever on a host that died."""
    client = _cluster_client()
    if client is None:
        return True
    try:
        client.wait_at_barrier(name, int(timeout_s * 1000))
        return True
    # lint: ok(typed-failure) — False is the typed result; callers map
    # it to a journaled EXIT_CLUSTER (the docstring contract)
    except Exception as e:  # noqa: BLE001 — timeout and UNAVAILABLE alike
        log.error("cluster barrier %r failed: %s", name, e)
        return False


def cluster_kv_set(key: str, value: str) -> bool:
    """Publish a value on the coordination service's KV store (rank 0's
    resume decision). Best-effort: False when the service is gone."""
    client = _cluster_client()
    if client is None:
        return False
    try:
        client.key_value_set(key, value)
        return True
    # lint: ok(typed-failure) — best-effort publish; False is the
    # typed result the caller branches on
    except Exception as e:  # noqa: BLE001
        log.error("cluster kv set %r failed: %s", key, e)
        return False


def cluster_kv_get(key: str, timeout_s: float = 120.0) -> str | None:
    """Blocking KV read (peers waiting for rank 0's resume decision).
    None on timeout / dead service."""
    client = _cluster_client()
    if client is None:
        return None
    try:
        return client.blocking_key_value_get(key, int(timeout_s * 1000))
    # lint: ok(typed-failure) — None is the typed timeout/dead-service
    # result; callers treat it as "no decision published"
    except Exception as e:  # noqa: BLE001
        log.error("cluster kv get %r failed: %s", key, e)
        return None


class KVBeatTransport:
    """Heartbeat transport over the jax.distributed KV store (the
    channel the cluster already trusts for init — no extra
    infrastructure, works without shared storage). Beats are
    set-once sequence-numbered keys (the coordination service forbids
    overwrite); each publish prunes its own beats a window behind, so
    the store stays bounded. Readers use `latest_seq` (a directory
    listing), NEVER an exact key — a reader that armed late (the
    first-contact grace covers minutes of jit-compile skew) or fell
    behind must catch up from whatever history remains, not wedge on a
    pruned sequence number. A dead coordinator makes every call fail,
    which the HostHeartbeat treats as silence — the whole cluster then
    exits 87 within one deadline, the coordinated-restart property."""

    _PREFIX = "caffe_hb"
    _PRUNE_LAG = 16

    def __init__(self, client=None):
        self._client = client if client is not None else _cluster_client()
        if self._client is None:
            raise _no_cluster_error()

    def _key(self, host: int, seq) -> str:
        return f"{self._PREFIX}/{int(host)}/{seq}"

    def publish(self, host: int, seq: int) -> None:
        self._client.key_value_set(self._key(host, seq), "1")
        if seq >= self._PRUNE_LAG:
            try:
                self._client.key_value_delete(
                    self._key(host, seq - self._PRUNE_LAG))
            # lint: ok(typed-failure) — pruning is best-effort; the
            # store stays bounded either way (readers use latest_seq)
            except Exception:  # noqa: BLE001 — pruning is best-effort
                pass

    def latest_seq(self, host: int) -> int:
        """Newest beat sequence `host` has published, -1 when none
        (missing dirs list as empty)."""
        entries = self._client.key_value_dir_get(
            f"{self._PREFIX}/{int(host)}/")
        latest = -1
        for key, _value in entries:
            tail = key.rsplit("/", 1)[-1]
            if tail.isdigit():
                latest = max(latest, int(tail))
        return latest

    def farewell(self, host: int) -> None:
        self._client.key_value_set(self._key(host, "bye"), "1")

    def is_bye(self, host: int) -> bool:
        try:
            self._client.blocking_key_value_get(self._key(host, "bye"), 1)
            return True
        # lint: ok(typed-failure) — absence of the bye key IS the
        # False answer; the KV get has no non-raising miss spelling
        except Exception:  # noqa: BLE001
            return False


def _no_cluster_error():
    from ..utils import resilience
    return resilience.ClusterError(
        "no jax.distributed runtime: KVBeatTransport needs "
        "init_distributed first (or set CAFFE_TPU_HB_DIR for the "
        "shared-directory transport)")


def heartbeat_transport():
    """The heartbeat channel for this run: the shared-directory
    transport when CAFFE_TPU_HB_DIR is set (tests, suspect
    coordination service), else the coordination-service KV store."""
    import os

    from ..utils import resilience
    hb_dir = os.environ.get("CAFFE_TPU_HB_DIR", "")
    if hb_dir:
        return resilience.DirBeatTransport(hb_dir)
    return KVBeatTransport()


def cluster_generation() -> dict | None:
    """The generation record this worker was launched under (ISSUE 19,
    degraded-mode elasticity — docs/robustness.md): the elastic
    supervisor (resilience.supervise_elastic) exports the current
    generation's shape per child via env. None outside a min_hosts
    run (plain ISSUE 11 clusters and single-host runs), so every
    consumer degrades to today's behavior."""
    import os
    gen = os.environ.get("CAFFE_TPU_CLUSTER_GEN", "")
    hosts = os.environ.get("CAFFE_TPU_CLUSTER_HOSTS", "")
    if not gen or not hosts:
        return None
    try:
        return {
            "generation": int(gen),
            "hosts": [int(h) for h in hosts.split(",") if h != ""],
            "world_full": int(
                os.environ.get("CAFFE_TPU_WORLD_FULL", "0") or 0),
            "self": int(
                os.environ.get("CAFFE_TPU_CLUSTER_SELF", "-1") or -1),
        }
    except ValueError:
        return None


def publish_generation() -> bool:
    """Mirror the live generation record onto the coordination
    service's KV store at `caffe/cluster_gen` (rank 0, right after
    formation): peers and in-band tooling can read the cluster's
    current shape over the channel they already trust. The
    supervisor's shared `<prefix>.cluster/` directory stays the source
    of truth — the KV store dies with the cluster epoch, which is
    exactly when the generation protocol must keep running. False
    when this is not a generation-managed run (or the service is
    gone); best-effort either way."""
    gen = cluster_generation()
    if gen is None:
        return False
    import json
    return cluster_kv_set("caffe/cluster_gen",
                          json.dumps(gen, sort_keys=True))


def to_host_array(a, dtype=None) -> np.ndarray:
    """np.asarray that also works for arrays with REMOTE shards (multi-host
    ZeRO-1 slots / TP weights), used by snapshot weight + history export.

    Replicated arrays read a local replica — no collective, any rank may
    call alone. The allgather branch IS collective: every process must
    reach it, in the same order, with no interleaved training collectives
    (callers serialize against the step loop)."""
    if (isinstance(a, jax.Array) and not a.is_fully_addressable
            and not a.is_fully_replicated):
        from jax.experimental import multihost_utils
        a = multihost_utils.process_allgather(a, tiled=True)
    return np.asarray(a) if dtype is None else np.asarray(a, dtype)


def needs_collective_gather(tree) -> bool:
    """True if host-exporting `tree` involves a cross-process collective —
    i.e. some leaf's shards are neither locally addressable nor replicated."""
    return any(isinstance(a, jax.Array) and not a.is_fully_addressable
               and not a.is_fully_replicated
               for a in jax.tree.leaves(tree))


def node_rank() -> int:
    """Reference Clusters::node_rank."""
    return jax.process_index()


def node_count() -> int:
    """Reference Clusters::node_count."""
    return jax.process_count()


@dataclass
class MeshPlan:
    """A mesh plus the sharding rules the solver uses."""

    mesh: Mesh

    @classmethod
    def data_parallel(cls, devices=None) -> "MeshPlan":
        """All devices on the 'data' axis — the reference's (only) strategy."""
        devs = np.asarray(devices if devices is not None else jax.devices())
        return cls(mesh=Mesh(devs.reshape(-1, 1), ("data", "model")))

    @classmethod
    def from_shape(cls, data: int, model: int = 1, devices=None) -> "MeshPlan":
        devs = np.asarray(devices if devices is not None else jax.devices())
        if devs.size != data * model:
            raise ValueError(
                f"mesh {data}x{model} needs {data * model} devices, "
                f"have {devs.size}")
        return cls(mesh=Mesh(devs.reshape(data, model), ("data", "model")))

    @property
    def n_data(self) -> int:
        return self.mesh.shape["data"]

    # -- shardings ------------------------------------------------------
    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def batch_sharded(self, ndim: int, axis: int = 0) -> NamedSharding:
        spec = [None] * ndim
        spec[axis] = "data"
        return NamedSharding(self.mesh, P(*spec))

    def shard_feeds(self, feeds, batch_axis: int = 0):
        """Place a feed pytree with the batch axis sharded over 'data'.
        Batch dims must divide n_data (the reference rounds up with a
        warning, parallel.cpp:284-293; here sharding requires exactness).

        Single-host: plain device_put. Multi-host: each process passes its
        LOCAL portion of the batch (rank-striped by the Feeder) and the
        global array is assembled from process-local shards — the SPMD
        analogue of the reference's per-node DataReader partitions feeding
        one global allreduce domain."""
        if jax.process_count() > 1:
            def put(x):
                sharding = self.batch_sharded(x.ndim, batch_axis)
                return jax.make_array_from_process_local_data(sharding, x)
        else:
            def put(x):
                return jax.device_put(x, self.batch_sharded(x.ndim, batch_axis))
        return jax.tree.map(put, feeds)

    def replicate(self, tree):
        """Broadcast params/state to every device (the reference's startup
        ncclBcast of all weights, parallel.cpp:208-227)."""
        return jax.device_put(tree, self.replicated())

    def shard_feeds_or_replicate(self, feeds, batch_axis: int = 0):
        """shard_feeds with a replication fallback: returns (placed,
        sharded?) where sharded? is False when ANY leaf's batch dim
        doesn't divide n_data (the reference rounds its divide_batch up
        with a warning, parallel.cpp:284-293; SPMD sharding requires
        exactness, so e.g. an odd-sized test batch evaluates replicated
        instead of crashing). Used by the fused eval pipeline to put
        test super-batches on all chips (ISSUE 2)."""
        if all(getattr(x, "ndim", 0) > batch_axis
               and x.shape[batch_axis] % self.n_data == 0
               for x in jax.tree.leaves(feeds)):
            return self.shard_feeds(feeds, batch_axis=batch_axis), True
        return self.replicate(feeds), False

    def per_batch_shard(self, fn, *arrays):
        """Run `fn` on each device's batch shard of `arrays` under
        shard_map. This is how a Pallas kernel sits inside the
        GSPMD-partitioned train/eval step: the partitioner refuses Mosaic
        custom calls ("Mosaic kernels cannot be automatically
        partitioned"), so per-sample kernels (LRN, single-device flash
        attention) take the batch split explicitly — exact, since no
        sample reads another. Operands are replicated over 'model'. A
        batch the 'data' axis does not divide (an eval batch placed by
        shard_feeds_or_replicate) runs replicated instead."""
        divisible = all(a.shape[0] % self.n_data == 0 for a in arrays)
        spec = P("data") if divisible else P()
        # check_vma=False: pallas_call's internal slicing mixes varying
        # and unvarying operands in ways the vma checker rejects
        return jax.shard_map(fn, mesh=self.mesh,
                             in_specs=(spec,) * len(arrays),
                             out_specs=spec, check_vma=False)(*arrays)

    # -- ZeRO-1 optimizer-state sharding (beyond the reference) ---------
    def zero_slot_sharding(self, shape) -> NamedSharding | None:
        """Sharding for an optimizer slot under zero_stage 1: dim 0 split
        over 'data' (the gradient-averaging axis doubles as the
        slot-partition axis, à la ZeRO/Deepspeed stage 1). Returns None —
        caller keeps the slot replicated — when dim 0 doesn't divide
        n_data (small biases) or the mesh has no data parallelism."""
        if self.n_data <= 1 or not shape or shape[0] % self.n_data:
            return None
        return NamedSharding(self.mesh,
                             P(*(["data"] + [None] * (len(shape) - 1))))

    # -- tensor parallelism (beyond the reference's DP-only surface) ----
    def param_sharding_rules(self, rules: dict[str, tuple]):
        """Declare per-layer weight shardings over the 'model' axis.

        rules: {layer_name: partition_spec_tuple | "rows" | per-param dict}:
          {"fc6": ("model", None)} (or the "rows" shorthand) shards fc6's
          weight dim 0 (output features) over 'model';
          {"moe1": {"w1": ("model",), "w2": ("model",), "b1": ("model",),
                    "b2": ("model",)}} gives expert parallelism — each
          listed param gets its own spec, unlisted params replicate.
        Returns a placement function for param pytrees.

        With params sharded and activations batch-sharded, XLA's GSPMD
        partitioner inserts the all-gather/reduce-scatter pattern of
        Megatron-style tensor parallelism automatically — the 'model' mesh
        axis becomes an intra-layer parallel domain while 'data' stays the
        gradient-averaging domain."""
        def place(params):
            out = {}
            for lname, lparams in params.items():
                rule = rules.get(lname)
                placed = {}
                for pname, arr in lparams.items():
                    if isinstance(rule, dict):
                        spec = rule.get(pname)
                        if spec is None:
                            placed[pname] = jax.device_put(
                                arr, self.replicated())
                        else:
                            if spec == "rows":
                                spec = ("model",)
                            elif isinstance(spec, str):
                                raise ValueError(
                                    f"per-param rule for {lname}/{pname} "
                                    f"must be a spec tuple or 'rows', got "
                                    f"{spec!r}")
                            spec = list(spec)[:arr.ndim]
                            spec += [None] * (arr.ndim - len(spec))
                            placed[pname] = jax.device_put(
                                arr, NamedSharding(self.mesh, P(*spec)))
                    elif rule is not None and pname == "weight":
                        if rule == "rows":
                            spec = ["model"] + [None] * (arr.ndim - 1)
                        else:
                            spec = list(rule)[:arr.ndim]
                            spec += [None] * (arr.ndim - len(spec))
                        placed[pname] = jax.device_put(
                            arr, NamedSharding(self.mesh, P(*spec)))
                    elif (rule is not None and pname == "bias"
                          and arr.ndim >= 1
                          and (rule == "rows"
                               or (len(rule) > 0 and rule[0] == "model"))):
                        # output-dim-sharded weight => the per-output bias
                        # shards the same way (InnerProduct (out,in) and
                        # Convolution (Cout,Cin/g,kh,kw) both carry the
                        # output dim first)
                        placed[pname] = jax.device_put(
                            arr, NamedSharding(self.mesh,
                                               P(*(["model"]
                                                   + [None] * (arr.ndim - 1)))))
                    else:
                        placed[pname] = jax.device_put(arr, self.replicated())
                out[lname] = placed
            return out
        return place
