"""True multi-process distributed tests: two coordinated CPU processes stand
in for two TPU-VM hosts (each with 2 virtual devices), validating the paths
single-process tests cannot — `jax.distributed` bootstrap in `mpi.start()`,
the per-host communicator split across real process boundaries, host ring
collectives over real sockets between processes, and the parameter server
spanning processes.

This is the closest no-cluster analogue of the reference's multi-node
HOSTFILE runs (reference: scripts/test_cpu.sh:36-57).
"""

import os
import socket
import subprocess
import sys
import textwrap

import pytest

from conftest import COLLECTIVE_TIMEOUT_FLAG

# Two full JAX interpreters boot and train: ~a minute of wall time.
pytestmark = pytest.mark.heavy

_WORKER = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=2 "
                               "__TIMEOUT_FLAG__")
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")
    sys.path.insert(0, {repo!r})

    import numpy as np

    coord, pid, nproc = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
    hc_ports = [int(p) for p in sys.argv[4].split(",")]
    ps_port = int(sys.argv[5])

    import torchmpi_tpu as mpi

    mpi.start(with_tpu=False, coordinator_address=coord,
              num_processes=nproc, process_id=pid)
    assert jax.process_count() == nproc, jax.process_count()
    assert mpi.size() == 2 * nproc, mpi.size()

    # Per-host communicator level was pushed automatically (2 hosts).
    assert mpi.need_inter_node_collectives()
    world = mpi.stack.world()
    assert world.num_nodes() == nproc
    host_level = mpi.stack.at(1)
    assert host_level.num_groups == nproc

    # Data-parallel step over the cross-process mesh: global batch sharded
    # over all 4 devices, grads pmean'd -- identical params everywhere.
    from torchmpi_tpu.collectives import eager
    x = eager.fill_by_rank(world, (8,))
    out = mpi.allreduce(x)
    # Multi-controller: only locally-addressable shards can be fetched.
    local = np.asarray(out.addressable_shards[0].data)
    assert np.allclose(local, sum(range(2 * nproc))), local

    # Grouped eager collective across process boundaries: one group per
    # host (the tree/hierarchical grouping shape).
    groups = tuple(tuple(range(h * 2, h * 2 + 2)) for h in range(nproc))
    gout = eager.allreduce(world, eager.fill_by_rank(world, (4,)),
                           groups=groups)
    glocal = np.asarray(gout.addressable_shards[0].data)
    my_group = groups[pid]
    assert np.allclose(glocal, sum(my_group)), glocal

    # Host-plane ring across the two real processes: the full collective
    # set (reference: lib/collectives.cpp:126-455 over real sockets).
    from torchmpi_tpu.collectives.hostcomm import HostCommunicator
    endpoints = [("127.0.0.1", p) for p in hc_ports]
    hc = HostCommunicator(pid, nproc, endpoints)
    a = np.full((101,), float(pid + 1), np.float32)
    hc.allreduce(a)
    assert np.allclose(a, sum(r + 1 for r in range(nproc))), a[0]
    b = np.full((7,), float(pid), np.float64)
    hc.broadcast(b, root=1)
    assert np.allclose(b, 1.0), b[0]
    rr = np.full((33,), float(pid + 1), np.float32)
    hc.reduce(rr, op="sum", root=0)
    if pid == 0:
        assert np.allclose(rr, sum(r + 1 for r in range(nproc))), rr[0]
    else:
        assert np.allclose(rr, float(pid + 1)), rr[0]
    sr = np.full((9,), float(pid * 100), np.float32)
    hc.sendreceive(sr, 0, nproc - 1)
    if pid == nproc - 1:
        assert np.allclose(sr, 0.0), sr[0]
    ag = hc.allgather(np.arange(pid + 1, dtype=np.int32))
    expect_ag = np.concatenate([np.arange(r + 1, dtype=np.int32)
                                for r in range(nproc)])
    assert np.array_equal(ag, expect_ag), ag
    h_async = hc.allreduce_async(np.full((64,), 1.0, np.float32))
    assert np.allclose(h_async.wait(), float(nproc))
    hc.barrier()

    # Selector host column across REAL processes: attach the ring to the
    # communicator and let payload-keyed resolution route a numpy
    # allreduce through the hostcomm cell (placement = payload residence;
    # mean folds as sum / size in the cell).
    from torchmpi_tpu.collectives import selector
    world.host_ring = hc
    fn_h = selector.resolve("allreduce", payload=np.zeros(1))
    out_h = fn_h(world, np.full((17,), float(pid + 1), np.float32),
                 op="mean")
    want_h = sum(r + 1 for r in range(nproc)) / nproc
    assert np.allclose(out_h, want_h), out_h[0]
    hc.barrier()

    # Identity helpers: the process/device plane contract.
    assert mpi.process_rank() == pid and mpi.process_count() == nproc
    assert mpi.local_device_ranks() == [2 * pid, 2 * pid + 1]

    # Engine across processes: compiled mode trains on the cross-process
    # mesh (batch staging contributes only locally-owned rows via
    # make_array_from_process_local_data), then check_with_allreduce
    # validates the replica-consistency invariant multi-controller
    # (reference: test_cpu.sh HOSTFILE runs + init.lua:372-395).
    from torchmpi_tpu.engine import AllReduceSGDEngine
    from torchmpi_tpu import nn as mpinn
    from torchmpi_tpu.models import mlp
    from torchmpi_tpu.utils.data import Dataset, ShardedIterator
    import jax.numpy as jnp

    world4 = mpi.stack.world()
    rng = np.random.RandomState(0)
    ds = Dataset(x=rng.rand(128, 16).astype(np.float32),
                 y=(np.arange(128) % 4).astype(np.int32))
    it = ShardedIterator(ds, global_batch=32, num_shards=world4.size, seed=7)
    params = mlp.init(jax.random.PRNGKey(0), in_dim=16, hidden=(32,),
                      n_classes=4)
    engine = AllReduceSGDEngine(mlp.loss_fn, lr=0.1, comm=world4,
                                mode="compiled")
    state = engine.train(params, it, epochs=2)
    l_first = float(np.asarray(state["loss"].addressable_shards[0].data))
    assert np.isfinite(l_first), l_first

    # Replica-consistency on a rank-major pytree across the 2 processes.
    rm = eager.shard(world4, [np.full((5,), 3.25, np.float32)] * world4.size)
    mpinn.check_with_allreduce([rm], world4)
    try:
        bad = eager.fill_by_rank(world4, (5,))   # fill=rank: replicas differ
        mpinn.check_with_allreduce([bad], world4)
        raise SystemExit("check_with_allreduce missed divergent replicas")
    except AssertionError:
        pass

    # Parameter server spanning processes: process 0 hosts the shard server.
    from torchmpi_tpu import parameterserver as ps
    if pid == 0:
        from torchmpi_tpu.parameterserver import native
        sid = native.lib().tmpi_ps_server_start(ps_port)
        assert sid > 0
    hc.barrier()   # server up before clients connect
    ps.init_cluster(endpoints=[("127.0.0.1", ps_port)], start_server=False)
    if pid == 0:
        t = ps.init(np.zeros((11,), np.float32), initial="zero")
    hc.barrier()   # shard created before peers push
    # Both processes address the same deterministic instance id.
    t2 = ps.PSTensor(1, (11,), np.float32)
    ps.send(t2, np.full((11,), float(pid + 1), np.float32), rule="add").wait()
    ps.barrier()
    hc.barrier()   # all peers' pushes applied before anyone reads
    h, outv = ps.receive(t2)
    h.wait()
    assert np.allclose(outv, sum(r + 1 for r in range(nproc))), outv[0]

    # Checkpoint-resume split-brain guard: divergent per-process checkpoint
    # views (here: per-process dirs, only rank 0 saved) must raise on every
    # rank instead of resuming inconsistently.
    import tempfile
    from torchmpi_tpu.utils import checkpoint as ckpt_mod
    mydir = tempfile.mkdtemp(prefix="ckpt_p" + str(pid) + "_")
    if pid == 0:
        ckpt_mod.save(mydir, 5, [np.ones((2,), np.float32)])
    try:
        ckpt_mod.resume_or_init(ckpt_mod.CheckpointManager(mydir),
                                [jnp.zeros((2,))])
        raise SystemExit("divergent checkpoint views not detected")
    except RuntimeError:
        pass
    hc.close()

    # Heartbeat liveness across REAL process boundaries (runtime/failure.py;
    # the in-process tests cover death detection, this proves the UDP
    # plane between separate interpreters).
    import time as _time
    from torchmpi_tpu.runtime import HeartbeatMonitor
    hb_ports = [int(p) for p in sys.argv[6].split(",")]
    hb_eps = [("127.0.0.1", p) for p in hb_ports]
    mon = HeartbeatMonitor(pid, hb_eps, interval=0.05)
    deadline = _time.monotonic() + 10
    peer = 1 - pid
    while _time.monotonic() < deadline and peer not in mon.heard_peers():
        _time.sleep(0.05)
    assert mon.alive_peers() == [peer], (mon.alive_peers(), mon.dead_peers())
    assert mon.heard_peers() == [peer], "never heard from peer process"
    mon.stop()

    mpi.stop()
    print("WORKER-{{}}-OK".format(pid))
""")


_WORKER_MATRIX = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=2 "
                               "__TIMEOUT_FLAG__")
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")
    sys.path.insert(0, __REPO__)

    import numpy as np
    import jax.numpy as jnp

    coord, pid, nproc = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
    hc_ports = [int(p) for p in sys.argv[4].split(",")]
    ps_port = int(sys.argv[5])
    ckpt_dir = sys.argv[6]

    import torchmpi_tpu as mpi
    from torchmpi_tpu import parallel
    from torchmpi_tpu.models import llama, mlp

    mpi.start(with_tpu=False, coordinator_address=coord,
              num_processes=nproc, process_id=pid)
    world = mpi.stack.world()
    assert world.size == 4

    # --- 1. dp x tp llama training step across the process boundary -----
    # (the no-cluster analogue of the reference's HOSTFILE shape loop,
    # scripts/test_gpu.sh:42-50)
    mesh = parallel.make_mesh({"dp": 2, "tp": 2}, devices=world.devices)
    cfg = llama.tiny(vocab=64, seq=16)
    params = llama.shard_params(
        llama.init(jax.random.PRNGKey(0), cfg), mesh, cfg)
    step = llama.make_train_step(cfg, mesh, lr=5e-2)
    rng = np.random.RandomState(0)
    toks = rng.randint(0, cfg.vocab, (8, 16)).astype(np.int32)
    from jax.sharding import NamedSharding, PartitionSpec as P
    bsh = NamedSharding(mesh, P("dp"))
    tg = np.roll(toks, -1, 1)
    # Every process holds the full batch; each builds only the shards its
    # devices own (the multi-controller staging contract).
    tokens = jax.make_array_from_callback(toks.shape, bsh,
                                          lambda idx: toks[idx])
    targets = jax.make_array_from_callback(tg.shape, bsh,
                                           lambda idx: tg[idx])
    opt_state = None
    losses = []
    for _ in range(6):
        params, opt_state, loss = step(params, opt_state, tokens, targets)
        losses.append(float(np.asarray(
            loss.addressable_shards[0].data)))
    assert all(np.isfinite(l) for l in losses), losses
    assert losses[-1] < losses[0], losses

    print("MATRIX-%d-part1" % pid, flush=True)
    # --- 2. checkpoint save + agreed_latest_step resume ------------------
    from torchmpi_tpu.utils import checkpoint as ckpt
    from torchmpi_tpu.engine import AllReduceSGDEngine
    from torchmpi_tpu.utils.data import Dataset, ShardedIterator
    ds = Dataset(x=rng.rand(64, 16).astype(np.float32),
                 y=(np.arange(64) % 4).astype(np.int32))
    it = ShardedIterator(ds, global_batch=16, num_shards=world.size, seed=3)
    mparams = mlp.init(jax.random.PRNGKey(1), in_dim=16, hidden=(16,),
                       n_classes=4)
    engine = AllReduceSGDEngine(mlp.loss_fn, lr=0.1, comm=world,
                                mode="compiled")
    state = engine.train(mparams, it, epochs=1)
    # Shared filesystem: only process 0 writes; both must agree on latest.
    mgr = ckpt.CheckpointManager(ckpt_dir)
    if pid == 0:
        ckpt.save(ckpt_dir, state["t"], {"params": state["params"]},
                  metadata={"t": state["t"]})
    # Order the write before both processes' agreement check.
    from torchmpi_tpu.collectives.hostcomm import HostCommunicator
    endpoints = [("127.0.0.1", p) for p in hc_ports]
    hc = HostCommunicator(pid, nproc, endpoints)
    hc.barrier()
    agreed = ckpt.agreed_latest_step(ckpt_dir)
    assert agreed == state["t"], (agreed, state["t"])
    p2, _, t2 = ckpt.resume_or_init(mgr, state["params"])
    assert t2 == state["t"]
    for a, b in zip(jax.tree.leaves(p2), jax.tree.leaves(state["params"])):
        av = np.asarray(a.addressable_shards[0].data)
        bv = np.asarray(b.addressable_shards[0].data)
        assert np.allclose(av, bv), "resume changed params"

    print("MATRIX-%d-part2" % pid, flush=True)
    # --- 3. EASGD over the PS with the 2 processes as ONE sync-DP group --
    # (the combo path: only DP rank 0 is a PS client; integrated params
    # broadcast over the DP plane -- reference update.lua:103-112)
    from torchmpi_tpu import parameterserver as ps
    from torchmpi_tpu.parameterserver.update import EASGDUpdate
    if pid == 0:
        from torchmpi_tpu.parameterserver import native
        sid = native.lib().tmpi_ps_server_start(ps_port)
        assert sid > 0
    hc.barrier()
    ps.init_cluster(endpoints=[("127.0.0.1", ps_port)], start_server=False)
    wparams = mlp.init(jax.random.PRNGKey(2), in_dim=16, hidden=(16,),
                       n_classes=4)
    upd = EASGDUpdate(beta=0.9, size=1, init_delay=1, update_frequency=2,
                      rank=0, fence=hc.barrier, dp=hc)
    grad_fn = jax.jit(jax.value_and_grad(mlp.loss_fn))
    lit = ShardedIterator(ds, global_batch=8 * nproc, num_shards=nproc,
                          seed=5)
    stepn = 0
    epoch_means = []
    for epoch in range(6):
        elosses = []
        for xb, yb in lit:
            lval, grads = grad_fn(wparams, (xb[pid], yb[pid]))
            # sync-DP inside the group: host-plane allreduce + mean.
            leaves = [np.array(np.asarray(g), dtype=np.float32)
                      for g in jax.tree.leaves(grads)]
            for a in leaves:
                hc.allreduce(a)
            flat, treedef = jax.tree.flatten(grads)
            grads = jax.tree.unflatten(treedef, [
                jnp.asarray(a / nproc, dtype=f.dtype)
                for a, f in zip(leaves, flat)])
            wparams = jax.tree.map(lambda p, g: p - 0.1 * g, wparams, grads)
            wparams = upd.update(wparams, grads, stepn)
            stepn += 1
            elosses.append(float(lval))
        epoch_means.append(sum(elosses) / len(elosses))
    wparams = upd.flush(wparams)
    assert all(np.isfinite(m) for m in epoch_means), epoch_means
    assert epoch_means[-1] < epoch_means[0], epoch_means
    # In-group replica consistency after the DP broadcast.
    local = np.concatenate([np.asarray(x, np.float32).ravel()
                            for x in jax.tree.leaves(wparams)])
    summed = local.copy()
    hc.allreduce(summed)
    assert np.allclose(summed, nproc * local, atol=1e-5), \\
        "EASGD DP replicas diverged"
    hc.barrier()
    hc.close()
    mpi.stop()
    print("MATRIX-%d-OK" % pid)
""")


def _free_ports(n):
    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    return ports



def _launch_workers(script_path, argv_per_pid, tag, timeout,
                    env_per_pid=None):
    """Shared 2-process launch harness: spawn, collect, assert rc 0 and the
    per-worker sentinel; kill survivors on timeout.  ``env_per_pid``
    optionally layers per-worker env vars over the base environment."""
    base = {k: v for k, v in os.environ.items()
            if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    procs = [
        subprocess.Popen([sys.executable, str(script_path), *argv],
                         stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                         text=True,
                         env={**base, **(env_per_pid[i] if env_per_pid
                                         else {})})
        for i, argv in enumerate(argv_per_pid)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=timeout)
            outs.append(out)
    except subprocess.TimeoutExpired:
        for p in procs:
            if p.poll() is None:
                p.kill()
            # Recover each worker's buffered output (sentinel progress
            # prints localize the hang) and reap the killed process.
            try:
                out, _ = p.communicate(timeout=10)
                outs.append(out)
            except Exception:  # noqa: BLE001 - best-effort diagnostics
                pass
        pytest.fail(f"{tag} workers timed out:\n" + "\n".join(outs))
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"{tag} worker {pid} failed:\n{out}"
        assert f"{tag}-{pid}-OK" in out, out


def test_two_process_distributed(tmp_path):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = tmp_path / "worker.py"
    script.write_text(_WORKER.format(repo=repo)
                      .replace("__TIMEOUT_FLAG__", COLLECTIVE_TIMEOUT_FLAG))
    coord_port, hc0, hc1, ps_port = _free_ports(4)
    from torchmpi_tpu.runtime.failure import free_udp_ports
    hb0, hb1 = free_udp_ports(2)
    coord = f"127.0.0.1:{coord_port}"
    _launch_workers(script, [
        [coord, str(pid), "2", f"{hc0},{hc1}", str(ps_port), f"{hb0},{hb1}"]
        for pid in range(2)], tag="WORKER", timeout=150)


def test_two_process_parallelism_matrix(tmp_path):
    """The round-3 shape matrix across REAL process boundaries (the
    no-cluster analogue of the reference's HOSTFILE loop,
    scripts/test_gpu.sh:42-50): a dp x tp llama training step, checkpoint
    save + agreed_latest_step resume on the shared filesystem, and an
    EASGD-over-sync-DP loop where only DP rank 0 talks to the parameter
    server — all multi-controller, no single-process fallback."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = tmp_path / "worker_matrix.py"
    script.write_text(_WORKER_MATRIX.replace("__REPO__", repr(repo))
                      .replace("__TIMEOUT_FLAG__", COLLECTIVE_TIMEOUT_FLAG))
    coord_port, hc0, hc1, ps_port = _free_ports(4)
    ckpt_dir = str(tmp_path / "shared_ckpt")
    coord = f"127.0.0.1:{coord_port}"
    _launch_workers(script, [
        [coord, str(pid), "2", f"{hc0},{hc1}", str(ps_port), ckpt_dir]
        for pid in range(2)], tag="MATRIX", timeout=600)


_WORKER_HIER = textwrap.dedent("""
    import sys

    import numpy as np

    sys.path.insert(0, "{repo}")
    from torchmpi_tpu.collectives.hostcomm import HierarchicalHostCommunicator

    rank = int(sys.argv[1])
    groups = [[int(r) for r in g.split(",")] for g in sys.argv[2].split(";")]
    intra = [("127.0.0.1", int(p)) for p in sys.argv[3].split(",")]
    inter = [("127.0.0.1", int(p)) for p in sys.argv[4].split(",")]
    n = sum(len(g) for g in groups)

    hc = HierarchicalHostCommunicator(rank, groups, intra, inter,
                                      timeout_ms=60000)
    print("HIER-{{}}-wired".format(rank), flush=True)

    a = np.full((513,), float(rank), np.float32)
    hc.allreduce(a)
    assert np.allclose(a, n * (n - 1) / 2), a[:4]

    b = np.full((33,), float(rank), np.float32)
    hc.broadcast(b, root=n - 1)
    assert np.allclose(b, n - 1), b[:4]

    c = np.full((21,), float(rank), np.float32)
    hc.reduce(c, root=1)
    if rank == 1:
        assert np.allclose(c, n * (n - 1) / 2), c[:4]
    else:
        assert np.allclose(c, float(rank)), c[:4]

    hc.barrier()
    hc.close()
    print("HIER-{{}}-OK".format(rank))
    """)


@pytest.mark.parametrize("groups", ["0,1;2,3", "0,1,2;3,4,5"],
                         ids=["2x2", "2x3"])
def test_hierarchical_host_plane_real_processes(tmp_path, groups):
    """The two-level host plane across REAL process boundaries (VERDICT
    r04 item 5): per-group intra rings x a roots ring, wired from separate
    interpreters over loopback TCP — allreduce/broadcast/reduce/barrier
    algebra holds at 2x2 and 2x3 (reference: the hierarchical CPU-plane
    composition, docs/communicators.md:24-32)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = tmp_path / "hier_worker.py"
    script.write_text(_WORKER_HIER.format(repo=repo))
    glist = [[int(r) for r in g.split(",")] for g in groups.split(";")]
    n = sum(len(g) for g in glist)
    ports = _free_ports(n + len(glist))
    intra = ",".join(str(p) for p in ports[:n])
    inter = ",".join(str(p) for p in ports[n:])
    _launch_workers(script, [
        [str(pid), groups, intra, inter] for pid in range(n)],
        tag="HIER", timeout=120)


_ENV_WORKER = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=2 "
                               "__TIMEOUT_FLAG__")
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")
    sys.path.insert(0, {repo!r})

    pid = int(sys.argv[1])

    import torchmpi_tpu as mpi

    # NO explicit coordinates: start() must read the launcher-plumbed env
    # (the scripts/launch.sh contract).
    mpi.start(with_tpu=False)
    assert jax.process_count() == 2, jax.process_count()
    assert mpi.process_rank() == pid and mpi.process_count() == 2
    assert mpi.size() == 4, mpi.size()
    mpi.stop()
    print(f"ENVWORKER-{{pid}}-OK", flush=True)
""")


def test_env_only_distributed_bringup(tmp_path):
    """mpi.start() with NO explicit coordinates initializes the process
    group from the env vars scripts/launch.sh plumbs
    (JAX_COORDINATOR_ADDRESS + JAX_NUM_PROCESSES/JAX_PROCESS_ID) — jax
    itself reads only the coordinator address, so lifecycle.start must
    pass the world shape through (round-5 fix: the documented generic-host
    flow raised 'Number of processes must be defined')."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = tmp_path / "env_worker.py"
    script.write_text(_ENV_WORKER.format(repo=repo)
                      .replace("__TIMEOUT_FLAG__", COLLECTIVE_TIMEOUT_FLAG))
    (coord_port,) = _free_ports(1)
    _launch_workers(
        script, [[str(pid)] for pid in range(2)], tag="ENVWORKER",
        timeout=150,
        env_per_pid=[
            {"JAX_COORDINATOR_ADDRESS": f"127.0.0.1:{coord_port}",
             "JAX_NUM_PROCESSES": "2", "JAX_PROCESS_ID": str(pid)}
            for pid in range(2)])
