"""An architecture's adapter: everything the harness knows about a model.

A configuration file names its architecture in its own `model_type` key (the
one Hugging Face gives it), and `cells.load_model` finds
`models/<model_type>.py` under the manifest's `paths` and loads it by path.
No other file of the harness names an architecture, a trial class or a key
of a model's `config.json`. So a `model_config` PR adds files and edits none:
this adapter (its plain reference inside it), the configuration with its
`serve` / `train` / `optimizer` groups, traffic, limits read from
`python3 -m benchmarks.control`, and a reader per new kernel.

The adapter is a module of plain functions:

  dims(config) -> Dims
      The static sizes under the adapter's own names, from the keys the
      published config spells them with. Hashable (a jit's static argument);
      only this module reads what is inside.
  init_params(key, dims) -> params
      Float32 weights from one key, in the program's own tree layout and by
      the program's own splits of the key: serving hands them to the engine,
      and the training reference starts from the numbers the trainer draws.
  logits(params, tokens, gather, dims, quant=None) -> [R, G, V]
      The plain forward: tokens [R, T] (prompt then served tokens,
      right-padded) to the logits at the positions gather [R, G]. The
      adapter owns its blocking: `reference.replay_gaps` hands it a few rows
      of the mix's widest request and assumes no [T, T] matrix fits.
  loss_and_grads(params, tokens, dims, quant=None, rows=2) -> (loss, grads)
      Mean next-token loss of the batch [B, S+1] and its gradient, `rows`
      rows at a time.
  serving(config, serve) -> mapping
      What `determined_tpu.serve.task.build_model` takes; the replica is
      built from `build_replica`'s objects, and a new family registers
      itself in the program.
  hparams(config) -> mapping
      The model's own hyperparameters for the trial class that the
      configuration's `train.trial` names ("<file> <Class>", loaded by
      path). The harness adds what is the cell's: `seq_len`, batch, mesh,
      optimizer, `attention_impl`, `remat`, `scan_unroll`, the seeded rows.
  work(dims) -> {params_per_token, attn_layers, q_heads, kv_heads, head_dim}
      What the readers count with (`stats.work(run)`): the parameters one
      token's forward multiplies (of sparse experts those it is routed to,
      not all), the layers that attend, and the attention's own widths,
      which need not multiply to the model's.

`reference.py` jits `logits` and `loss_and_grads` (`dims`, `quant`, `rows`
static) and traces them under `jax.default_matmul_precision("highest")`, so
an adapter sets neither. Every matmul of both goes through
`reference.matmul(x, w, quant)` and every other rounding through
`reference.ROUND[quant]`: that is what lets `benchmarks.control` put the
int8 and fp8 controls in the program's place for a new cell. The adapter
imports nothing of the program and takes nothing the program has made.
"""


class Dims(dict):
    """A mapping of sizes that can be a static jit argument."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))
