"""A series' mean or peak as a percentage of a counter:
params {"series", "counter", "take": "mean" | "max"}."""


def read(obs, params):
    values = obs["series"].get(params["series"]) or []
    whole = obs["counters"].get(params["counter"])
    if not values or not whole:
        return None
    top = max(values) if params.get("take") == "max" \
        else sum(values) / len(values)
    return 100.0 * top / whole
