"""The route step's share of the chip's bf16 peak: encoder forward
FLOPs over the real (unmasked) first-page tokens of every document
routed in the window, over the window's seconds times the peak, in
percent. Cells without an encoder have nothing to read."""
import flops


def read(run):
    enc = run.config.get("encoder")
    if enc is None or run.peak is None or not run.batches:
        return None
    work = sum(flops.encoder_flops(b["real_tokens"], enc)
               for b in run.batches)
    return 100.0 * work / (run.window_s * run.peak["flops_per_s"])
