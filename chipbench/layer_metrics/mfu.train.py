"""The whole train step's share of the chip's peak: model FLOPs per
token (recomputed work not counted) x tokens per second of the window,
over the peak bf16 FLOP/s of the chips used."""
from chipbench import flops


def read(record, cell, peaks):
    f = record["facts"]
    per_token = flops.train_flops_per_token(cell["config"], f["seq"])
    chips = cell["workload"]["chips"]
    return 100.0 * per_token * f["tokens_per_s"] / (
        chips * peaks["bf16_flops_per_s"])
