"""What one merged policy call of the sequence policy with grouped-query
attention in a pattern of window and full layers and routed experts
must compute and move, from the configuration's shapes and from what
the server counted of the calls: the counts the whole call's and one
scope's roofline shares divide by the device's time.

Counted by what is LIVE in the call (a padded row's work is the
implementation's, not the algorithm's), at the stated dtypes:
bfloat16 parameters and caches, float32 router bias and value head.
These are the LEAST any implementation has to do, so a share over 100%
would mean a fault in the time it is divided by:

- parameters: attention, router, shared expert, dense MLP, the norms
  and the two heads are read once a call whatever the rows; of the
  routed experts only those some live row chose (`experts_hit`, which
  the routed layers count a call and the server sums); of the embedding
  only the rows' own lines;
- the two caches, `2 x kv_heads x head_dim` numbers a token and layer:
  in a FULL layer per live row every token of the row's episode so far,
  the one this call writes among them (`cache_tokens_read`, the sum of
  the live rows' positions and one, which the server follows on the
  host); in a WINDOW layer the same capped at the ring
  (`window_tokens_read`, the sum of `min(position + 1, window)`);
- FLOPs: a multiply-add is 2; the matrices by the rows; a routed
  expert by the rows that chose it (`routed_rows_held`); attention per
  token read, layer and query head the score and the weighted sum over
  `head_dim` numbers each.

`per_call` is what a merged call carried in the mean: the deltas of
the server's counters over the calls they were counted in
(`readers/trace_counted_share.py` makes it), by the counters' names.

Nothing here knows a cell: the shapes come from the program's Config
(`seq_*`, `num_actions`), as `dots_counts.py` takes its own.
"""


def shapes(config):
  layers, pattern = config.seq_num_layers, config.seq_layer_pattern
  kinds = [pattern[i % len(pattern)] for i in range(layers)]
  return dict(
      layers=layers, dense=config.seq_first_dense_layers,
      full=kinds.count('G'), windowed=kinds.count('L'),
      window=config.seq_window, hidden=config.seq_hidden_size,
      heads=config.seq_num_heads, kv=config.seq_num_kv_heads,
      dim=config.seq_head_dim, mlp=config.seq_mlp_size,
      moe=config.seq_moe_size, routed=config.seq_routed_experts,
      held=config.seq_experts_held, shared=config.seq_shared_experts,
      vocab=config.num_actions, capacity=config.seq_cache_capacity)


def attention_matrices(s):
  """The query, key, value and output projections."""
  return (s['hidden'] * s['heads'] * s['dim'] +
          2 * s['hidden'] * s['kv'] * s['dim'] +
          s['heads'] * s['dim'] * s['hidden'])


def attention_parameters(s):
  """The matrices and the norms of a head's query and key."""
  return attention_matrices(s) + 2 * s['dim']


def ffn_parameters(s, width):
  return 3 * s['hidden'] * width


def layer_parameters(s, routed):
  """A block: attention, the two norms on its halves' outputs, and the
  dense MLP, or the router (with its bias), the shared expert and the
  routed experts held."""
  common = attention_parameters(s) + 2 * s['hidden']
  if not routed:
    return common + ffn_parameters(s, s['mlp'])
  return (common + s['hidden'] * s['routed'] + s['routed'] +
          ffn_parameters(s, s['moe'] * s['shared']) +
          s['held'] * ffn_parameters(s, s['moe']))


def parameters(config):
  """Embedding, blocks, final norm, untied policy head, value head."""
  s = shapes(config)
  return (s['dense'] * layer_parameters(s, False) +
          (s['layers'] - s['dense']) * layer_parameters(s, True) +
          2 * s['vocab'] * s['hidden'] + s['hidden'] + s['hidden'] + 1)


def token_bytes(config):
  """One token's keys and values in one layer, bfloat16."""
  s = shapes(config)
  return 2 * 2 * s['kv'] * s['dim']


def state_bytes_per_slot(config):
  """A slot's caches at their capacity, its rings, and its int32
  position."""
  s = shapes(config)
  return token_bytes(config) * (
      s['full'] * s['capacity'] + s['windowed'] * s['window']) + 4


def full_cache_bytes(config, per_call):
  """What the live rows read in the full layers: every token of their
  episodes, the one each writes among them."""
  return (per_call['cache_tokens_read'] * shapes(config)['full'] *
          token_bytes(config))


def window_cache_bytes(config, per_call):
  """The same in the window layers: their rings' live columns."""
  return (per_call['window_tokens_read'] * shapes(config)['windowed'] *
          token_bytes(config))


def cache_bytes(config, per_call):
  return (full_cache_bytes(config, per_call) +
          window_cache_bytes(config, per_call))


def attend_flops(config, per_call):
  s = shapes(config)
  reads = (per_call['cache_tokens_read'] * s['full'] +
           per_call['window_tokens_read'] * s['windowed'])
  return reads * s['heads'] * 2 * 2 * s['dim']


def experts_bytes(config, per_call):
  """The weights of the routed experts that some live row chose."""
  s = shapes(config)
  return per_call['experts_hit'] * 2 * ffn_parameters(s, s['moe'])


def _matrices(s):
  """The weights every live row is multiplied by, whatever the call
  carries: all but the routed experts (the value head's among them)."""
  routed = s['layers'] - s['dense']
  return (s['layers'] * attention_matrices(s) +
          s['dense'] * ffn_parameters(s, s['mlp']) +
          routed * (s['hidden'] * s['routed'] +
                    ffn_parameters(s, s['moe'] * s['shared'])) +
          s['vocab'] * s['hidden'] + s['hidden'])


def call_bytes(config, per_call):
  s = shapes(config)
  # Read whole every call: the matrices and the norms in bfloat16; the
  # routers' biases and the value head (weights and bias) in float32.
  norms = s['layers'] * (2 * s['dim'] + 2 * s['hidden']) + s['hidden']
  bf16 = _matrices(s) - s['hidden'] + norms
  f32 = (s['layers'] - s['dense']) * s['routed'] + s['hidden'] + 1
  return (2 * (bf16 + per_call['requests'] * s['hidden']) + 4 * f32 +
          experts_bytes(config, per_call) + cache_bytes(config, per_call))


def call_flops(config, per_call):
  s = shapes(config)
  return (2 * per_call['requests'] * _matrices(s) +
          2 * per_call['routed_rows_held'] * ffn_parameters(s, s['moe']) +
          attend_flops(config, per_call))
