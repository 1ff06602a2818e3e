"""Host time writing an admitted request's recurrent state into its lane
(span ``engine.scatter.state``, inside ``engine.scatter``) per request
admitted (span ``engine.admit``), in ms."""
from bench import program_spans as ps

STATE = "engine.scatter.state"


def read(obs):
    cap = ps.last_capture(obs)
    if cap is None or not cap.count(STATE) or not cap.count("engine.admit"):
        return None
    return cap.seconds_in(STATE) / cap.count("engine.admit") * 1e3
