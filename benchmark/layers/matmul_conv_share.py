"""`matmul_conv_share` (model step): share of the operations' time on the
device that convolutions and matrix products took (`trace_reduce.py`:
`is_matmul_or_conv`, self time).  The rest is bandwidth-bound work the chip's
matrix unit waits for."""


def read(obs):
    t = obs["trace"]
    if not t or not t["op_self_s"]:
        return None
    return 100.0 * t["matmul_conv_s"] / t["op_self_s"]
