"""A/B the compiled engine's DP gradient sync: GSPMD lowering vs the
explicit pallas ring (``use_pallas_collectives``) — the TPU analogue of the
reference's custom-ring-vs-NCCL comparison (reference: README.md:104-106,
honest about where the vendor path wins).

On one real chip (p=1) this measures the pure structural overhead of the
shard_map + flat-packing path against the plain pjit step — the ring
kernel itself shortcuts at p=1, so any delta is dispatch/restructure cost.
On the virtual CPU mesh (p=8) the ring runs the Pallas *interpreter*
(~1000x slow) — numbers there validate plumbing, not performance; keep
--batch/--hidden tiny so the epochs are short, and ignore the timings.

Run (real chip):
    python benchmarks/engine_ring_bench.py --steps 30
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

if os.environ.get("JAX_PLATFORMS") == "cpu":
    jax.config.update("jax_platforms", "cpu")

import numpy as np

import torchmpi_tpu as mpi
from torchmpi_tpu.engine import AllReduceSGDEngine
from torchmpi_tpu.models import mlp
from torchmpi_tpu.runtime import config
from torchmpi_tpu.utils.data import ShardedIterator, synthetic_mnist


def _timed_epochs(engine, state, it, epochs):
    """Timed epochs with a value-read fence at the end (the BASELINE.md
    protocol of rounds 2-5, whose set-up did not fence on
    block_until_ready; a value read fences everywhere)."""
    t0 = time.perf_counter()
    state = engine.train(state["params"], it, epochs=epochs)
    float(np.asarray(state["loss"].addressable_shards[0].data))
    return time.perf_counter() - t0, state


def bare_mode(args):
    """Bare compiled-step slope A/B — resolves ms-scale structure where a
    dispatch carries a large fixed cost (~30-60 ms each, drifting minute to
    minute, on the rounds 2-5 set-up; not measured on today's machine): the
    engine-loop form above pays one Python dispatch PER STEP, which swamps
    any sub-ms structural delta; here each measurement is one fenced window
    of n dispatched steps and the (T(n2)-T(n1))/(n2-n1) slope cancels the
    fixed overhead."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from torchmpi_tpu.runtime.communicator import RANK_AXIS

    mpi.start(with_tpu=jax.default_backend() == "tpu")
    comm = mpi.stack.world()
    mesh = comm.mesh()
    p = mesh.shape[RANK_AXIS]
    print(f"# bare-step slope, backend={jax.default_backend()} p={p}")

    rng = np.random.RandomState(0)
    B = args.batch
    x = jnp.asarray(rng.standard_normal((B, 28 * 28)).astype(np.float32))
    y = jnp.asarray(rng.randint(0, 10, (B,)).astype(np.int32))
    bsh = NamedSharding(mesh, P(RANK_AXIS))
    x, y = jax.device_put(x, bsh), jax.device_put(y, bsh)
    params0 = mlp.init(jax.random.PRNGKey(0),
                       hidden=(args.hidden, args.hidden))

    # Engine.train wants rank-major host batches for its warmup pass.
    hx = np.asarray(x).reshape(p, B // p, -1)
    hy = np.asarray(y).reshape(p, B // p)
    setups = {}
    for label, flag in (("gspmd", False), ("pallas_ring", True)):
        config.set("use_pallas_collectives", flag)
        engine = AllReduceSGDEngine(mlp.loss_fn, lr=0.1, mode="compiled")
        state = engine.train(jax.tree.map(np.asarray, params0), [(hx, hy)])
        step = engine._compiled_step
        pp, oo, loss = step(state["params"], state["opt_state"], x, y)
        setups[label] = [step, pp, oo]

    def run(label, n):
        step, pp, oo = setups[label]
        t0 = time.perf_counter()
        for _ in range(n):
            pp, oo, loss = step(pp, oo, x, y)
        float(loss)
        setups[label][1:] = [pp, oo]
        return time.perf_counter() - t0

    for label in setups:
        run(label, 20)                    # warm past compile/autotune
    per = {k: [] for k in setups}
    for trial in range(args.trials):
        for label in setups:
            t_a, t_b = run(label, 10), run(label, 40)
            s = (t_b - t_a) / 30
            per[label].append(s)
            print(f"trial{trial} {label:>12}: {s * 1e3:8.3f} ms/step")
    med = {k: sorted(v)[len(v) // 2] for k, v in per.items()}
    delta = med["pallas_ring"] - med["gspmd"]
    print(f"median gspmd {med['gspmd']*1e3:.3f} ms  "
          f"ring {med['pallas_ring']*1e3:.3f} ms")
    print(f"ring - gspmd (structural): {delta * 1e3:+.3f} ms/step")
    mpi.stop()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=512)
    ap.add_argument("--hidden", type=int, default=2048)
    ap.add_argument("--trials", type=int, default=3,
                    help="interleaved A/B trials; the MEDIAN delta is the "
                         "reported number (throughput may drift minute "
                         "to minute, so single-pass A/Bs lie)")
    ap.add_argument("--bare", action="store_true",
                    help="bare compiled-step slope instead of the engine "
                         "loop (resolves sub-ms structural deltas)")
    args = ap.parse_args()
    if args.bare:
        bare_mode(args)
        return

    mpi.start(with_tpu=jax.default_backend() == "tpu")
    world = mpi.stack.world()
    p = world.size
    print(f"# backend={jax.default_backend()} p={p}")

    ds = synthetic_mnist(n=args.batch * 8)
    params = mlp.init(jax.random.PRNGKey(0), hidden=(args.hidden, args.hidden))

    # Build + warm both paths first, then interleave timed windows.
    setups = {}
    epochs = 1
    for label, flag in (("gspmd", False), ("pallas_ring", True)):
        config.set("use_pallas_collectives", flag)
        it = ShardedIterator(ds, global_batch=args.batch, num_shards=p, seed=1)
        epochs = max(1, args.steps // len(it))
        engine = AllReduceSGDEngine(mlp.loss_fn, lr=0.1, mode="compiled")
        state = engine.train(jax.tree.map(np.asarray, params), it, epochs=1)
        float(np.asarray(state["loss"].addressable_shards[0].data))
        setups[label] = (flag, engine, state, it)

    per_step = {k: [] for k in setups}
    for trial in range(args.trials):
        for label, (flag, engine, state, it) in setups.items():
            config.set("use_pallas_collectives", flag)
            elapsed, state = _timed_epochs(engine, state, it, epochs)
            setups[label] = (flag, engine, state, it)
            s = elapsed / (epochs * len(it))
            per_step[label].append(s)
            print(f"trial{trial} {label:>12}: {s * 1e3:8.3f} ms/step")

    med = {k: sorted(v)[len(v) // 2] for k, v in per_step.items()}
    delta = med["pallas_ring"] - med["gspmd"]
    print(f"median gspmd {med['gspmd']*1e3:.3f} ms  "
          f"ring {med['pallas_ring']*1e3:.3f} ms")
    print(f"ring - gspmd: {delta * 1e3:+.3f} ms/step "
          f"({100 * delta / med['gspmd']:+.1f}%)")
    mpi.stop()


if __name__ == "__main__":
    main()
