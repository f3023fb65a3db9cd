"""mix_roofline: the Eq.-4 mix's share of its roofline, in percent: the
least time the chip needs for the algorithm's work of the mix over the
window's last graph (max of FLOPs over the bf16 peak and bytes over the
HBM bandwidth, `flops.mix_flops` / `flops.mix_bytes`), over the device
time, from the trace, of one call of the cell's mix kernel
(`graph_mix` or `sparse_graph_mix`) on that graph. The mix is
memory-bound at these sizes: bytes set the least time."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
import flops  # noqa: E402


def read(run):
    s = run.get("call_s", {}).get("mix_call")
    if not s or run.get("mix_bytes") is None:
        return None
    least, _ = flops.roofline_s(run["mix_flops"], run["mix_bytes"],
                                run["peak"])
    return 100.0 * least / s
