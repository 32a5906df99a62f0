"""`collective_exposed_ms` (collectives): the part of `collective_ms` during
which no compute operation ran on that device: what the backward pass does
not hide.  On TPU v5 lite with this JAX the all-reduces of the ResNet step
are synchronous operations of the TensorCore itself (PR 22: 100 a step,
nothing beside them), so there the two metrics are equal by construction."""


def read(obs):
    t = obs["trace"]
    if not t or not t["steps"]:
        return None
    return 1e3 * t["collective_exposed_s"] / t["steps"]
