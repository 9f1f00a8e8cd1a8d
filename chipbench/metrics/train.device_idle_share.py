"""Share of the traced window in which no op ran on the device, averaged
over the chips: the host loop of ``trainer.fit`` (data wait, dispatch,
the per-step metric sync) holding the chip back. Moves
``train_tokens_per_s``."""


def read(run):
    if run["kind"] != "train":
        return None
    return 100.0 * (1.0 - run["busy_s"] / run["window_s"])
