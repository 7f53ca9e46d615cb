"""A percentile of a named series: params {"series", "pct"}."""

from benchmark import stats


def read(obs, params):
    values = obs["series"].get(params["series"]) or []
    if len(values) < int(params.get("min_samples", 1)):
        return None
    return stats.percentile(values, float(params["pct"]))
