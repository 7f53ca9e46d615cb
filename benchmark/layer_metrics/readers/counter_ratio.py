"""One counter as a percentage of another:
params {"part", "whole"}."""


def read(obs, params):
    part = obs["counters"].get(params["part"])
    whole = obs["counters"].get(params["whole"])
    if not part or not whole:
        return None
    return 100.0 * part / whole
