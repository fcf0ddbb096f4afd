"""The whole train step's share of the chips' bf16 peak: useful FLOPs per
sample (MLPs and interaction in three passes, bag sums and row updates)
times samples per second of the traced window, over chips x peak."""


def read(r):
    return (100.0 * r.samples_per_s * r.flops_per_sample
            / (r.chips * r.peak["bf16_flops_per_s"]))
