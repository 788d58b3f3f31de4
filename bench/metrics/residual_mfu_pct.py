"""Whole step: a residual network's FLOPs for the images completed in the
window, over the window's seconds times the chip's bfloat16 peak, in
percent. The FLOPs are 2 * MAC per image from the unpadded layer shapes
(``bench/residual_net.py``); padding and recomputation do not count."""
from bench import residual_net


def read(trace, record):
    if record["window_s"] <= 0 or record["images"] == 0:
        return None
    flops = 2.0 * residual_net.macs_per_image(record["cfg"]) * record["images"]
    peak = record["peaks"]["flops_per_s"]["bfloat16"]
    return 100.0 * flops / (record["window_s"] * peak)
