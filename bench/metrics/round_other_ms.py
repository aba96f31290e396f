"""round_other_ms: device-busy ms per round of the traced window outside the
ops scoped ``adgda.local`` and ``adgda.gossip``: the dual, the telemetry,
unscoped ops and loop bookkeeping. With round_local_ms and round_gossip_ms
it sums to the busy time per round. Moves train_tokens_per_s."""
from yardstick.scopes import round_split


def read(run):
    split = round_split(run)
    return None if split is None else split["other"]
