"""A number the run observed directly, by its path in the
observations (set-up time, the compile ledger, the program's own
counters as they stood when the window closed), times `scale`."""


def read(obs, path, scale=1.0):
  value = obs
  for key in path:
    if not isinstance(value, dict) or key not in value:
      return None
    value = value[key]
  return float(value) * scale
