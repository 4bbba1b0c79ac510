"""The fast_features kernel's share of its roofline: the least time the
chip needs for the work that the prepare calls started inside the traced
window needed (``flops.fast_features_work``), over the device seconds of
the kernel's operation in the trace, in percent."""
import flops

#: the kernel's operation in the trace (its Pallas custom-call)
OP = "fast_features_kernel"


def read(run):
    max_len = run.config.get("encoder", {}).get("max_len", 0)
    return run.roofline(OP, [flops.fast_features_work(r["stream_tokens"],
                                                      max_len)
                             for r in run.started("prepare")])
