"""What one merged policy call of the sequence policy with
power-retention layers must compute and move, from the configuration's
shapes: the counts the whole call's and the state kernel's roofline
shares divide by the device's time.

Counted by the rows that are LIVE in the call (a padded row's work is
the implementation's, not the algorithm's), at the stated dtypes:
bfloat16 parameters, float32 state. These are the LEAST any
implementation has to do, so a share over 100% would mean a fault in
the time it is divided by:

- parameters: every block's and the two heads' weights are read once a
  call whatever the rows (2 bytes each); of the embedding only the
  rows' own lines;
- the retention state: per session and layer, for each key-value head
  the symmetric degree-2 terms of a head-sized key (D (D + 1) / 2)
  times (D values + 1 normaliser), float32, read once and written once
  a call;
- FLOPs: a multiply-add is 2; the projections by the rows; the state
  update (decay, outer product, sum: 3 an element) and each query
  head's read of it (2 an element).

Nothing here knows a cell: the shapes come from the program's Config
(`seq_*`, `num_actions`), as `flops.py` takes the image agent's.
"""


def shapes(config):
  return dict(
      layers=config.seq_num_layers, hidden=config.seq_hidden_size,
      heads=config.seq_num_heads, kv_heads=config.seq_num_kv_heads,
      head_dim=config.seq_head_dim, mlp=config.seq_mlp_size,
      vocab=config.num_actions)


def block_parameters(s):
  """One block: q, k, v, gate and output projections, the SwiGLU's
  three, two RMSNorms over the hidden size and two over a head."""
  attn = s['hidden'] * s['head_dim'] * (s['heads'] + 2 * s['kv_heads'])
  return (attn + s['hidden'] * s['kv_heads'] +
          s['heads'] * s['head_dim'] * s['hidden'] +
          3 * s['hidden'] * s['mlp'] + 2 * s['hidden'] + 2 * s['head_dim'])


def parameters(config):
  """Embedding, blocks, final norm, untied policy head, value head."""
  s = shapes(config)
  return (s['layers'] * block_parameters(s) + 2 * s['vocab'] * s['hidden']
          + s['hidden'] + s['hidden'] + 1)


def state_terms(s):
  """Float32 numbers of one session's state in one layer."""
  d = s['head_dim']
  return s['kv_heads'] * (d * (d + 1) // 2) * (d + 1)


def state_bytes_per_slot(config):
  s = shapes(config)
  return 4 * s['layers'] * state_terms(s)


def state_bytes(config, rows):
  """Read once and written once, for every live row."""
  return 2 * rows * state_bytes_per_slot(config)


def call_bytes(config, rows):
  s = shapes(config)
  read_whole = (s['layers'] * block_parameters(s) +
                s['vocab'] * s['hidden'] + 2 * s['hidden'] + 1)
  return (2 * (read_whole + rows * s['hidden']) +
          state_bytes(config, rows))


def call_flops(config, rows):
  s = shapes(config)
  matmul = (s['layers'] * (block_parameters(s) - 2 * s['hidden'] -
                           2 * s['head_dim']) +
            s['vocab'] * s['hidden'] + s['hidden'])
  state = s['layers'] * state_terms(s) * (
      3 + 2 * s['heads'] // s['kv_heads'])
  return rows * (2 * matmul + state)
