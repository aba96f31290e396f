"""round_local_ms: device ms per round of the traced window in ops the
compiled step scopes ``adgda.local`` (the local oracle and optimizer).
Moves train_tokens_per_s."""
from yardstick.scopes import round_split


def read(run):
    split = round_split(run)
    return None if split is None else split["local"]
