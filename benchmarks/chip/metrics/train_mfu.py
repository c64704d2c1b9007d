"""Whole train step: the model's forward+backward flops per triple
(``flops_per_triple`` of ``references/<model>.py``, optimizer excluded,
in the record's ``work``) times the window's triples/s, over the chips'
bfloat16 peak, in %."""


def read(rec):
    t = rec.get("train")
    if not t or t["triples_per_s"] <= 0:
        return None
    flops = rec["work"]["flops_per_triple"]
    peak = rec["peaks"]["bf16_flops_per_s"] * rec["chips"]
    return 100.0 * flops * t["triples_per_s"] / peak
