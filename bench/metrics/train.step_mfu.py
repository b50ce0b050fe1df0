"""Training: the background train step's share of the chip's peak bf16
rate: forward and backward operations of one step (no recomputation
counted) over the step program's mean device time. Moves
``background_tokens_per_s`` in training cells."""


def read(rec):
    bg = rec["traffic"].get("background") or {}
    prog = rec["trace"]["reduced"]["programs"].get("jit_train_step")
    if bg.get("kind") != "train" or not prog:
        return None
    work = rec["arch"].train_step_flops(rec["dm"], bg["batch"], bg["seq"])
    t = prog["device_s"] / prog["count"]
    return 100.0 * work / rec["peaks"]["bf16_flops_per_s"] / t
