"""Model FLOP/s utilization of the whole event: the client gradients'
forward and backward FLOPs (``bench.flops.mlp_train_flops``) per event
times events per second, over the chips' bf16 peak."""


def read(rec):
    if rec["unit"] != "events" or not rec["peaks"]:
        return None
    rate = rec["work"] / rec["window_s"]
    return 100.0 * rec["flops_per_unit"] * rate / (
        rec["peaks"]["bf16_flops"] * rec["chips"])
