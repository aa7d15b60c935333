"""The fused Pallas sweep kernels' share of their roofline, from the trace.

Every ``fused_move`` and ``fused_split`` call in the traced window counts
the bytes and operations of its own operand shapes (the largest 2-D operand
is its ``rows x d`` tile; lpabench/bytemodel.py), over the kernels' summed
device time.  Bytes bound both kernels on a v5e.  No call, no reading.
"""
from lpabench import bytemodel, tracing

MODELS = {"fused_move": bytemodel.fused_move_call,
          "fused_split": bytemodel.fused_split_call}


def read(run, win, summary):
    if summary is None:
        return None
    nbytes = flops = seconds = 0.0
    for kernel, model in MODELS.items():
        for ev in tracing.kernel_calls(summary, kernel):
            shape = tracing.tile_shape(ev.name)
            if shape is None:
                continue
            b, f = model(*shape)
            nbytes, flops = nbytes + b, flops + f
            seconds += ev.dur_ns / 1e9
    if seconds == 0:
        return None
    return bytemodel.roofline_share(nbytes, flops, seconds, run.peaks)
