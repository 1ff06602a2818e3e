"""Host time in the engine's admission (``ServingEngine._admit``: prefill,
lane scatter, first token) per request admitted, in ms."""


def read(obs):
    n = obs.get("counts", {}).get("admitted")
    if not n:
        return None
    return obs["spans"]["admit"] / n * 1e3
