"""`kernel_calls` (kernels): the Mosaic kernel calls in the timed step's
program, the occurrences of `tpu_custom_call` in the compiled executable's
text, which every token runner counts once in set-up
(`ctx.counters["kernel_calls"]`).  The text names an instruction once: the
kernels of an inlined layer stand there for each layer, those of a scanned
layer's body once whatever the trips.  It falls when a remat policy keeps a
kernel's output where it replayed the kernel (`llama._wrap_remat`), and
rises when a kernel takes the place of XLA's own lowering.  It says how many
kernels the program holds, not how often one runs nor how long:
`flash_ms` and `moe_experts_ms` read the time."""


def read(obs):
    return obs["counters"].get("kernel_calls")
