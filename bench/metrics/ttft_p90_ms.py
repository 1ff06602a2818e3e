"""90th percentile of time to first token over the requests due in the
window, in ms: the tail beside the gated median, from fewer than ten
requests beyond it, so recorded and not bounded."""


def read(obs):
    return obs.get("ttft_p90_ms")
