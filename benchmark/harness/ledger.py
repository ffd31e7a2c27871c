"""Compilation as the run saw it, from jax.monitoring's own events.

Every backend compile request is one record: the function's name, the
seconds it took, whether the persistent cache answered it, and the
phase it fell in ('setup' until the window opens, 'window' while it is
measured, 'after' from its close). `correct` needs the window's count
to be zero; the entry layer's metrics read the set-up's.
"""

import threading

_COMPILE = '/jax/core/compile/backend_compile_duration'
_HIT = '/jax/compilation_cache/cache_hits'
_MISS = '/jax/compilation_cache/cache_misses'


class CompileLedger:

  def __init__(self):
    self.phase = 'setup'
    self.records = []  # {'phase', 'name', 'secs', 'cache'}
    self._lock = threading.Lock()
    self._thread = threading.local()

  # The cache's hit or miss event fires inside the compile request it
  # belongs to, on the same thread, before the request's duration.
  def _event(self, event, **kwargs):
    if event == _HIT:
      self._thread.cache = 'hit'
    elif event == _MISS:
      self._thread.cache = 'miss'

  def _duration(self, event, duration, **kwargs):
    if event != _COMPILE:
      return
    cache = getattr(self._thread, 'cache', None) or 'uncached'
    self._thread.cache = None
    with self._lock:
      self.records.append({
          'phase': self.phase, 'secs': float(duration), 'cache': cache,
          'name': str(kwargs.get('fun_name', '?'))})

  def install(self):
    import jax
    jax.monitoring.register_event_listener(self._event)
    jax.monitoring.register_event_duration_secs_listener(self._duration)

  def summary(self, phase):
    """{'requests', 'hits', 'misses', 'uncached', 'secs',
    'compiled_names'} of one phase; `compiled_names` are the programs
    the cache did not answer."""
    with self._lock:
      rows = [r for r in self.records if r['phase'] == phase]
    by = lambda cache: [r for r in rows if r['cache'] == cache]  # noqa: E731
    return {
        'requests': len(rows),
        'hits': len(by('hit')),
        'misses': len(by('miss')),
        'uncached': len(by('uncached')),
        'secs': sum(r['secs'] for r in rows),
        'compiled_names': sorted(
            r['name'] for r in rows if r['cache'] != 'hit'),
    }
