"""collective_exposed_share: time of collective ops on the device's op line (where no compute op runs
beside them), over the traced window; mean over the chips."""



layer = "sharding"
unit = "%"
moves = "train_tokens_per_s"
source = "device_trace"


def read(run):
    trace = run.get("trace")
    if not trace or trace["devices"] < 2:
        return None
    return trace["exposed_collective_s"] / trace["window_s"] * 100.0
