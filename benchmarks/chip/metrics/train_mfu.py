"""Whole train step: TransE forward+backward flops per triple
(``work.transe_flops_per_triple``, optimizer excluded) times the window's
triples/s, over the chips' bfloat16 peak, in %."""
from benchmarks.chip import work


def read(rec):
    t = rec.get("train")
    if not t or t["triples_per_s"] <= 0:
        return None
    w = rec["work"]
    flops = work.transe_flops_per_triple(w["dim"], w["num_negs"])
    peak = rec["peaks"]["bf16_flops_per_s"] * rec["chips"]
    return 100.0 * flops * t["triples_per_s"] / peak
