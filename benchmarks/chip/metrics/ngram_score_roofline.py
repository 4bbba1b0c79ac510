"""The ngram_score kernel's share of its roofline: the least time the
chip needs for the work that the probe calls started inside the traced
window needed (``flops.ngram_work``), over the device seconds of the
kernel's operation in the trace, in percent."""
import flops

#: the kernel's operation in the trace (its Pallas custom-call)
OP = "ngram_bleu_kernel"


def read(run):
    return run.roofline(OP, [flops.ngram_work(*r["probe_tokens"])
                             for r in run.started("complete")
                             if "probe_tokens" in r])
