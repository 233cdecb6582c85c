"""swa_fwd_roofline_pct.train (%): the required FLOPs of a step's
``mx_window_attn_fwd`` calls (``flops_trinity.window_kernel_flops``: the
band's query-key pairs x 4 head_dim x heads, forward and recomputation, in
every ``W`` block) over those instructions' self time and the chip's
bfloat16 peak.  Compute-bound; tiles computed and masked away are not
required work.  Nothing without a device trace or such a kernel."""
import os

from chipbench.files import load_module

KERNEL = "mx_window_attn_fwd"


def read(evidence):
    here = os.path.dirname(os.path.abspath(__file__))
    flops = load_module(os.path.dirname(here), "flops_trinity.py")
    return flops.window_roofline_pct(evidence, KERNEL)
