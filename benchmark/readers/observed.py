"""A count or time the driver observed: ``100 * key / per`` style arithmetic only."""


def read(result, summary, ctx, key, scale=1.0, per=None):
    obs = result.observed
    if obs.get(key) is None or (per is not None and not obs.get(per)):
        return None
    value = float(obs[key]) * scale
    return value / float(obs[per]) if per is not None else value
