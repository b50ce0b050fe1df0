"""Model: the bulk (background) prefill's share of the chip's peak bf16
rate: the operations of one bulk chunk of the mix's ``seq`` tokens (logits
at its last position) over the bulk-prefill program's (``jit_prefill``)
mean device time. Moves ``background_tokens_per_s`` in ingestion cells."""


def read(rec):
    bg = rec["traffic"].get("background") or {}
    prog = rec["trace"]["reduced"]["programs"].get("jit_prefill")
    if bg.get("kind") != "ingest" or not prog:
        return None
    work = rec["arch"].prefill_flops(rec["dm"], bg["seq"])
    t = prog["device_s"] / prog["count"]
    return 100.0 * work / rec["peaks"]["bf16_flops_per_s"] / t
