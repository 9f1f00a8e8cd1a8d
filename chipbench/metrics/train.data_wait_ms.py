"""Mean ``data_wait`` span of ``trainer.fit`` per step in the traced
window (the wait on the batch iterator). Moves ``train_tokens_per_s``."""


def read(run):
    if run["kind"] != "train":
        return None
    waits = [r["dur_us"] for r in run["records"]
             if r.get("kind") == "span" and r["name"] == "data_wait"]
    if not waits:
        return None
    return sum(waits) / len(waits) / 1e3
