"""Smoke sizes for CPU runs: each configuration and traffic file holds the
keys it overrides at smoke size in a ``"smoke"`` object (its ``"why"``
says why, and is not merged)."""


def at_smoke(doc: dict, path: str) -> dict:
    """``doc``, the file at ``path``, with its ``smoke`` keys set; a dict
    is merged one level deep, as a mix's ``arrivals`` or a configuration's
    ``engine``."""
    if not isinstance(doc.get("smoke"), dict):
        raise KeyError(f"{path} has no \"smoke\" object")
    out = dict(doc)
    for k, v in doc["smoke"].items():
        if k == "why":
            continue
        out[k] = {**doc.get(k, {}), **v} if isinstance(v, dict) else v
    return out


def shrink(cell: dict, cfg: dict, mix: dict):
    """A cell's configuration and traffic (as ``bench.run.resolve`` gives
    them) at smoke size."""
    return (at_smoke(cfg, f"bench/configs/{cell['config']}.json"),
            at_smoke(mix, f"bench/traffic/{cell['name']}.json"))
