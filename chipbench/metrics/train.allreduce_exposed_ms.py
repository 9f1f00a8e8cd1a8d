"""Per optimizer step, the device time of collective ops (the gradient
``pmean`` of the data-parallel step) during which no other op runs on
that device, averaged over the chips. Only where the step spans chips.
Moves ``train_tokens_per_s``."""
from chipbench import xplane


def read(run):
    if run["kind"] != "train" or run["chips"] < 2 \
            or not xplane.op_count(run["trace"], xplane.COLLECTIVE.pattern):
        return None
    return xplane.exposed_collective_s(run["trace"]) / run["steps"] * 1e3
