"""Share of its HBM roofline that the fused reduce+compress kernel reaches:
the bytes it must move a round (``kernel_bytes.roundtrip_bytes``, from the
config's layout) over the union of its ops' device time a round times the
chip's HBM bandwidth (``peaks.json``), in %, for the device whose kernel
time is longest. The kernel's ops are those whose ``op_name`` ends
``reduce_compress_roundtrip/pallas_call`` (``vmap(reduce_compress_roundtrip)
/pallas_call`` where the program maps the kernel over pods); where the
traced window holds none, there is nothing to read (None)."""

import re

from benchmarks.chip import kernel_bytes, legs, trace

KERNEL = re.compile(r"(?:^|/)(?:vmap\()?reduce_compress_roundtrip\)?/pallas_call$")


def read(ctx):
    names = ctx["op_names"]
    per_device = []
    for ops in ctx["trace"].ops.values():
        mine = [(o.start, o.end) for o in ops
                if KERNEL.search(names.get(legs.instruction(o.name), ""))]
        busy = trace.total(trace.union(trace.clip(mine, ctx["lo"],
                                                  ctx["hi"])))
        if busy:
            per_device.append(busy)
    if not per_device:
        return None
    seconds = max(per_device) / ctx["rounds"] / 1e9
    moved = kernel_bytes.roundtrip_bytes(ctx["config"])
    return 100.0 * moved / (seconds * ctx["peak"]["hbm_bytes_per_s"])
