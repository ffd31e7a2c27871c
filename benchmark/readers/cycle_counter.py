"""What the program's always-on cycle records (PR 37: `server.stats()`
`call_*`, `batcher_wait_ms`, `latency_p95_ms`; `fleet.stats()`
`step_*`, `group_steps`) say of the window. One of:

- `per=[source, key]`: the counter's change from open to close by
  another counter's (a cumulative `*_ms` by the calls or steps it
  covers: the window's MEAN, whatever its median);
- `per='second'`: its change a second of window;
- `per='close'`: the number as it stood when the window closed;

times `scale` (100 for a share in %). Where the base did not move the
ratio reads 0.0 (a window without excess has no share of it under
anything), not None.

None, which is an error on the chip, where the run has no such
counters at all or where a program that keeps cycle records (it has
the key `since` names) lacks `key`: a name that moved. A program from
BEFORE the records (`since` is missing too: the parent commit of the
PR that brought them, under this file) has nothing to read and reads
0.0, so that its run ends with a result.
"""


def read(obs, source, key, since, per='second', scale=1.0):
  counters = obs.get('counters') or {}
  opened = counters.get('open', {}).get(source)
  closed = counters.get('close', {}).get(source)
  if opened is None or closed is None:
    return None
  if since not in closed:
    return 0.0
  if key not in closed or key not in opened:
    return None
  if per == 'close':
    return closed[key] * scale
  delta = closed[key] - opened[key]
  if per == 'second':
    return delta / obs['window_seconds'] * scale
  base_source, base_key = per
  base = (counters['close'][base_source][base_key] -
          counters['open'][base_source][base_key])
  return delta / base * scale if base else 0.0
