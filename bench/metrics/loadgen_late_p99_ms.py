"""loadgen_late_p99_ms: how late the load generator submitted requests
against their schedule, 99th percentile, in ms. Moves serve_tokens_per_s."""
from yardstick.stats import percentile


def read(run):
    if run.kind != "serve" or not run.lateness:
        return None
    return 1e3 * percentile(run.lateness, 99)
