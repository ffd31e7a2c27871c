"""What one of the program's own counters did over the window: its
change from open to close, per second of window (`per='second'`) or per
unit of another counter's change (`per=[source, key]`)."""


def _delta(obs, source, key):
  counters = obs.get('counters')
  if not counters or source not in counters.get('open', {}):
    return None
  return counters['close'][source][key] - counters['open'][source][key]


def read(obs, source, key, per='second'):
  delta = _delta(obs, source, key)
  if delta is None:
    return None
  if per == 'second':
    return delta / obs['window_seconds']
  base = _delta(obs, *per)
  if not base:
    return None
  return delta / base
