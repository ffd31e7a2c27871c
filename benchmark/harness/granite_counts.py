"""What one merged policy call of the sequence policy with Mamba-2 layers
beside full attention layers and routed experts (Granite 4.0-H's
hybrid) must compute and move, from the configuration's shapes and from
what the server counted of the calls: the counts the whole call's and
one scope's roofline shares divide by the device's time.

Counted by what is LIVE in the call (a padded row's work is the
implementation's, not the algorithm's), at the stated dtypes: bfloat16
parameters, conv windows and cache, float32 SSM state, dt bias, A_log,
D and value head. These are the LEAST any implementation has to do, so
a share over 100% would mean a fault in the time it is divided by:

- parameters: the mixers, norms, routers, shared experts and the tied
  embedding (the head reads it whole; a row's own line is among it) are
  read once a call whatever the rows; of the routed experts only those
  some live row chose (`experts_hit`, which the routed layers count a
  call and the server sums);
- a Mamba layer's state, `H x P x N` float32 numbers a row, read and
  written by every live row (`ssm_rows_stepped`: live rows times Mamba
  layers, which the mixers count a call); its conv window, `(K - 1) x
  (H P + 2 N)` bfloat16 numbers, the same;
- the full layers' cache, `2 x kv_heads x head_dim` numbers a token and
  layer: per live row every token of the row's episode so far, the one
  this call writes among them (`cache_tokens_read`, the sum of the live
  rows' positions and one, which the server follows on the host);
- FLOPs: a multiply-add is 2; the matrices by the rows; a routed
  expert by the rows that chose it (`routed_rows_held`); attention per
  token read, layer and query head the score and the weighted sum over
  `head_dim` numbers each; a Mamba state's element 5 a row (decay,
  input, their sum, and the multiply-add of `S C`), a conv channel 2 a
  tap.

`per_call` is what a merged call carried in the mean: the deltas of
the server's counters over the calls they were counted in
(`readers/trace_counted_share.py` makes it), by the counters' names.

Nothing here knows a cell: the shapes come from the program's Config
(`seq_*`, `num_actions`), as `exaone_counts.py` takes its own.
"""


def shapes(config):
  layers, pattern = config.seq_num_layers, config.seq_layer_pattern
  kinds = [pattern[i % len(pattern)] for i in range(layers)]
  heads, dim = config.seq_ssm_heads, config.seq_ssm_head_dim
  return dict(
      layers=layers, mamba=kinds.count('M'), full=kinds.count('G'),
      hidden=config.seq_hidden_size, heads=config.seq_num_heads,
      kv=config.seq_num_kv_heads, dim=config.seq_head_dim,
      ssm_heads=heads, ssm_dim=dim, inner=heads * dim,
      state=config.seq_ssm_state, width=config.seq_conv_width,
      channels=heads * dim + 2 * config.seq_ssm_state,
      moe=config.seq_moe_size, routed=config.seq_routed_experts,
      held=config.seq_experts_held, shared=config.seq_shared_experts,
      vocab=config.num_actions, capacity=config.seq_cache_capacity)


def mamba_matrices(s):
  """The input projection (z, xBC, dt) and the output projection."""
  return (s['hidden'] * (2 * s['inner'] + 2 * s['state'] + s['ssm_heads'])
          + s['inner'] * s['hidden'])


def mamba_parameters(s):
  """The matrices, the conv's taps and bias, dt_bias, A_log, D and the
  gated norm's weight."""
  return (mamba_matrices(s) + (s['width'] + 1) * s['channels'] +
          3 * s['ssm_heads'] + s['inner'])


def attention_parameters(s):
  """The query, key, value and output projections."""
  return (s['hidden'] * s['heads'] * s['dim'] +
          2 * s['hidden'] * s['kv'] * s['dim'] +
          s['heads'] * s['dim'] * s['hidden'])


def ffn_parameters(s, width):
  return 3 * s['hidden'] * width


def routed_parameters(s):
  """The router, the shared expert and the routed experts held."""
  return (s['hidden'] * s['routed'] +
          ffn_parameters(s, s['moe'] * s['shared']) +
          s['held'] * ffn_parameters(s, s['moe']))


def layer_parameters(s, kind):
  """A block: its mixer, its two norms, its routed layer."""
  mixer = mamba_parameters(s) if kind == 'M' else attention_parameters(s)
  return mixer + 2 * s['hidden'] + routed_parameters(s)


def parameters(config):
  """The blocks, the tied embedding, the final norm, the value head."""
  s = shapes(config)
  return (s['mamba'] * layer_parameters(s, 'M') +
          s['full'] * layer_parameters(s, 'G') +
          s['vocab'] * s['hidden'] + s['hidden'] + s['hidden'] + 1)


def state_bytes_per_row_and_layer(config):
  """One row's SSM state in one Mamba layer, float32."""
  s = shapes(config)
  return 4 * s['ssm_heads'] * s['ssm_dim'] * s['state']


def conv_bytes_per_row_and_layer(config):
  """One row's conv window in one Mamba layer, bfloat16."""
  s = shapes(config)
  return 2 * (s['width'] - 1) * s['channels']


def token_bytes(config):
  """One token's keys and values in one full layer, bfloat16."""
  s = shapes(config)
  return 2 * 2 * s['kv'] * s['dim']


def state_bytes_per_slot(config):
  """A slot's Mamba states and conv windows, its caches at their
  capacity, and its int32 position."""
  s = shapes(config)
  return (s['mamba'] * (state_bytes_per_row_and_layer(config) +
                        conv_bytes_per_row_and_layer(config)) +
          s['full'] * s['capacity'] * token_bytes(config) + 4)


def state_bytes(config, per_call):
  """What the state update must move a call: every live row's state in
  every Mamba layer, read and written."""
  return 2 * per_call['ssm_rows_stepped'] * state_bytes_per_row_and_layer(
      config)


def cache_bytes(config, per_call):
  """What the live rows read in the full layers."""
  return (per_call['cache_tokens_read'] * shapes(config)['full'] *
          token_bytes(config))


def experts_bytes(config, per_call):
  """The weights of the routed experts that some live row chose."""
  s = shapes(config)
  return per_call['experts_hit'] * 2 * ffn_parameters(s, s['moe'])


def _matrices(s):
  """The weights every live row is multiplied by, whatever the call
  carries: all but the routed experts (the value head's among them)."""
  return (s['mamba'] * mamba_matrices(s) +
          s['full'] * attention_parameters(s) +
          s['layers'] * (s['hidden'] * s['routed'] +
                         ffn_parameters(s, s['moe'] * s['shared'])) +
          s['vocab'] * s['hidden'] + s['hidden'])


def call_bytes(config, per_call):
  s = shapes(config)
  # Read whole every call: the matrices, the conv taps and bias, the
  # norms and the embedding in bfloat16; dt_bias, A_log, D and the value
  # head (weights and bias) in float32.
  norms = s['layers'] * 2 * s['hidden'] + s['mamba'] * s['inner'] + (
      s['hidden'])
  conv = s['mamba'] * (s['width'] + 1) * s['channels']
  bf16 = _matrices(s) - s['hidden'] + norms + conv
  f32 = s['mamba'] * 3 * s['ssm_heads'] + s['hidden'] + 1
  windows = 2 * per_call['ssm_rows_stepped'] * conv_bytes_per_row_and_layer(
      config)
  return (2 * bf16 + 4 * f32 + experts_bytes(config, per_call) +
          state_bytes(config, per_call) + windows +
          cache_bytes(config, per_call))


def call_flops(config, per_call):
  s = shapes(config)
  rows = per_call['ssm_rows_stepped']
  ssm = rows * (5 * s['ssm_heads'] * s['ssm_dim'] * s['state'] +
                2 * s['width'] * s['channels'])
  attend = (per_call['cache_tokens_read'] * s['full'] * s['heads'] * 2 *
            2 * s['dim'])
  return (2 * per_call['requests'] * _matrices(s) +
          2 * per_call['routed_rows_held'] * ffn_parameters(s, s['moe']) +
          attend + ssm)
