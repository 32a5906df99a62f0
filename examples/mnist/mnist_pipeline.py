"""Pipelined block-model-parallel MNIST — BASELINE config 4
("BlockSequential model-parallel CNN pipelined across TPU chips"): the
network body is partitioned into pipeline stages (the BlockSequential
partition promoted to a true micro-batch GPipe schedule across the pp axis);
embed and head stay outside the uniform-carrier pipeline.

Run:
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/mnist/mnist_pipeline.py
"""

import argparse

import jax
import jax.numpy as jnp
import numpy as np

import torchmpi_tpu as mpi
from torchmpi_tpu import parallel
from torchmpi_tpu.parallel import pipeline as pl
from torchmpi_tpu.utils.data import ShardedIterator, synthetic_mnist
from torchmpi_tpu.utils.meters import AverageValueMeter


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=5)
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--microbatches", type=int, default=4)
    ap.add_argument("--lr", type=float, default=0.02)
    ap.add_argument("--width", type=int, default=128)
    ap.add_argument("--stages", type=int, default=4)
    args = ap.parse_args()

    mpi.start()
    mesh = parallel.make_mesh({"pp": args.stages, "dp": -1})
    S, M, d = args.stages, args.microbatches, args.width
    print(f"pipeline: {S} stages x {M} micro-batches, width {d}")

    rng = np.random.RandomState(0)
    embed = {"w": jnp.asarray(rng.randn(784, d) * (2.0 / 784) ** 0.5, jnp.float32),
             "b": jnp.zeros((d,), jnp.float32)}
    head = {"w": jnp.asarray(rng.randn(d, 10) * (1.0 / d) ** 0.5, jnp.float32),
            "b": jnp.zeros((10,), jnp.float32)}
    stages = [{"w": jnp.asarray(rng.randn(d, d) * (2.0 / d) ** 0.5, jnp.float32),
               "b": jnp.zeros((d,), jnp.float32)} for _ in range(S)]
    body = pl.stage_sharding(mesh, pl.stack_stage_params(stages))

    def stage_fn(p, h):
        return jax.nn.relu(h @ p["w"] + p["b"]) + h  # residual keeps depth trainable

    pipe = pl.make_pipeline_fn(mesh, stage_fn, n_microbatches=M)

    def loss_fn(params, x, y):
        emb, body, hd = params
        h = x.reshape(x.shape[0], -1) @ emb["w"] + emb["b"]
        h = pl.unmicrobatch(pipe(body, pl.microbatch(h, M)))
        logits = h @ hd["w"] + hd["b"]
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))

    @jax.jit
    def step(params, x, y):
        loss, grads = jax.value_and_grad(loss_fn)(params, x, y)
        return jax.tree.map(lambda p, g: p - args.lr * g, params, grads), loss

    ds = synthetic_mnist(n=8192)
    it = ShardedIterator(ds, global_batch=args.batch, num_shards=1)
    params = (embed, body, head)
    for epoch in range(args.epochs):
        meter = AverageValueMeter()
        for xb, yb in it:
            params, loss = step(params, jnp.asarray(xb[0]), jnp.asarray(yb[0]))
            meter.add(loss)
        print(f"epoch {epoch}: loss {meter.mean:.4f}")

    @jax.jit
    def accuracy(params, x, y):
        emb, body_p, hd = params
        h = x.reshape(x.shape[0], -1) @ emb["w"] + emb["b"]
        h = pl.unmicrobatch(pipe(body_p, pl.microbatch(h, M)))
        pred = jnp.argmax(h @ hd["w"] + hd["b"], axis=-1)
        return jnp.mean(pred == y)

    accs = []
    for xb, yb in ShardedIterator(ds, global_batch=args.batch, num_shards=1,
                                  shuffle=False):
        accs.append(float(accuracy(params, jnp.asarray(xb[0]),
                                   jnp.asarray(yb[0]))))
    print(f"final accuracy {100 * np.mean(accs):.2f}%")
    mpi.stop()


if __name__ == "__main__":
    main()
