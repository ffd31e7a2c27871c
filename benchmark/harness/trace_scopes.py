"""The scope path of every device operation, as one more line of the
device plane.

`jax.named_scope` (and Flax's module names) end up in each HLO
instruction's `metadata.op_name`: `jit(anakin_step)/.../env/...`,
`jit(train_step)/transpose(jvp(ImpalaAgent))/torso/...`. On a TPU v5e
under jax 0.9.0 / libtpu 0.0.34 no stat of an `XLA Ops` event carries
it (PR 24, chip run: the event's own stats are `device_offset_ps`,
`device_duration_ps`, `Time Scale Multiplier`; its metadata's are
`hlo_category`, `program_id`, `symbol_id`, `flops`, `model_flops`,
`bytes_accessed`, `memory_access_breakdown`, `raw_bytes_accessed`,
`shape_with_layout`, none of which `jax.profiler.ProfileData` shows).
What the profile does hold is each compiled program's HLO: the plane
`/host:metadata` has one event metadata per program, named like the
`XLA Modules` events (`jit_train_step(<fingerprint>)`), with the
serialized `HloProto` as a bytes stat. So `add_scope_line` reads
those, maps instruction name -> `op_name` per program, and leaves for
each chip a line `XLA Op scopes` whose events have the start and
duration of the `XLA Ops` events and are NAMED by the scope path of
the instruction the event names, in the program whose `XLA Modules`
event encloses it. No reduction of `trace_reduce.py` reads that line;
`readers/trace_scope_share.py` does.

`/host:metadata` has no line, so `ProfileData` cannot reach it: the
few fields needed are read straight off the protobuf wire format
(field numbers from tsl's xplane.proto and xla's hlo.proto, in the
functions below). A fused operation carries the path of the
instruction at its root, so what XLA fused across a scope boundary
counts under one side.
"""

import re

import numpy as np

from benchmark.harness import trace_reduce

SCOPES_LINE = 'XLA Op scopes'
NO_SCOPE = '(no scope)'
METADATA_PLANE = b'/host:metadata'
_INSTRUCTION = re.compile(r'^%?([^\s=]+)')


def _varint(buf, i):
  value = shift = 0
  while True:
    byte = buf[i]
    i += 1
    value |= (byte & 0x7F) << shift
    if byte < 0x80:
      return value, i
    shift += 7


def _fields(buf):
  """(field number, value) of one protobuf message: an int for a
  varint, a memoryview for a length-delimited or fixed-width field."""
  i, n = 0, len(buf)
  while i < n:
    key, i = _varint(buf, i)
    wire = key & 7
    if wire == 0:
      value, i = _varint(buf, i)
    else:
      if wire == 2:
        size, i = _varint(buf, i)
      elif wire in (1, 5):
        size = 8 if wire == 1 else 4
      else:
        raise ValueError(f'protobuf wire type {wire}')
      value = buf[i:i + size]
      i += size
    yield key >> 3, value


def _field(buf, number):
  return [value for n, value in _fields(buf) if n == number]


def hlo_protos(path):
  """{program name: serialized HloProto} from the profile's
  `/host:metadata` plane. XSpace.planes = 1; XPlane.name = 2,
  .event_metadata = 4 (map: value = 2); XEventMetadata.name = 2,
  .stats = 5; XStat.bytes_value = 6."""
  with open(path, 'rb') as f:
    space = memoryview(f.read())
  out = {}
  for plane in _field(space, 1):
    if [bytes(v) for v in _field(plane, 2)] != [METADATA_PLANE]:
      continue
    for entry in _field(plane, 4):
      for metadata in _field(entry, 2):
        names = _field(metadata, 2)
        blobs = [blob for stat in _field(metadata, 5)
                 for blob in _field(stat, 6)]
        if names and blobs:
          out[bytes(names[0]).decode()] = blobs[0]
  return out


def op_names(hlo_proto):
  """{instruction name: op_name} of one program. HloProto.hlo_module
  = 1; HloModuleProto.computations = 3; HloComputationProto
  .instructions = 2; HloInstructionProto.name = 1, .metadata = 7;
  OpMetadata.op_name = 2."""
  out = {}
  for module in _field(hlo_proto, 1):
    for computation in _field(module, 3):
      for instruction in _field(computation, 2):
        name = op_name = None
        for number, value in _fields(instruction):
          if number == 1:
            name = bytes(value).decode()
          elif number == 7:
            op_name = [bytes(v).decode() for v in _field(value, 2)]
        if name and op_name:
          out[name] = op_name[0]
  return out


def add_scope_line(trace, path):
  """Returns how many operations got a scope path (0, and no line
  added, where the profile holds no program's HLO)."""
  programs = {name: op_names(blob)
              for name, blob in hlo_protos(path).items()}
  named = 0
  for chip, lines in trace.chips():
    ops = lines.get(trace_reduce.OPS_LINE)
    modules = lines.get(trace_reduce.MODULES_LINE)
    if ops is None or modules is None or not programs:
      continue
    # The execution each operation began in: the last to start before
    # it, if it has not ended (executions of one chip do not overlap).
    i = np.searchsorted(modules.start, ops.start, side='right') - 1
    inside = (i >= 0) & (
        ops.start < (modules.start + modules.dur)[np.maximum(i, 0)])
    plane, rows = f'/device:TPU:{chip}', []
    for j, name in enumerate(ops.names):
      scope = NO_SCOPE
      if inside[j]:
        instruction = _INSTRUCTION.match(name)
        scope = programs.get(modules.names[i[j]], {}).get(
            instruction.group(1) if instruction else None, NO_SCOPE)
      rows.append((plane, SCOPES_LINE, scope, ops.start[j], ops.dur[j]))
    named += sum(row[2] is not NO_SCOPE for row in rows)
    lines.update(trace_reduce.Trace.from_rows(rows).planes[plane])
  return named
