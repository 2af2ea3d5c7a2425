"""Architecture families: what the harness knows of a model's block.

A configuration file names its family (``"family": "dense_gqa"``).  The
harness loads ``chipbench/families/<family>.py`` by file path
(``spec.load_family``), as it loads a metric's reader, and reaches the
served weights, the plain reference and the counts of least work only
through that module.  No list of families is kept in code: a family is
a file, and a name that no file answers stops the run before any device
work, naming the families there are.

A family module provides:

- ``dims_of(conf)``: the shapes a configuration file states, as one
  hashable value (jitted code takes it as a static argument) with at
  least ``layers``, ``d`` (the residual width) and ``vocab``, which the
  readers common to every family use.
- ``hf_keys``: ``{published key: ModelConfig field}`` that the family
  adds to ``spec.HF_TO_REPRO``; each of these keys that the file holds
  has to equal that field of the built configuration.
- ``program_params(key, dims)``: the engine's parameter tree, drawn on
  the device from ``key`` in one jitted call, in the types it is served
  in.
- ``keep_masks(key, layers)``: the ``lean`` pair, [layers] bool masks of
  the attention and the MLP blocks whose routers lean to keeping.
- ``reference_run(conf, key, seqs, rows, lowp_modes)``: ``(results,
  facts)``.  ``results[lowp]`` is a ``reference.Result`` for each
  precision in ``lowp_modes`` (``True`` is the control, one precision
  step below the configuration): the next-token logits of each sequence
  at its ``rows``, the shares of attention and MLP gates kept, the KV
  entries each sequence stores and the share of gates against their
  lean.  ``facts`` is a dict of what else the reference saw (tokens per
  expert, say), which per-layer readers read as ``ctx.facts``; dense
  GQA's is empty.  The reference imports nothing of ``repro`` and takes
  nothing the program made.
- The counts of least work the readers use, each the least an exact
  implementation must do (``counts.py`` gives dense GQA's):
  ``token_flops(dims, ctx, attn_keep, mlp_keep, logits)``, operations
  of one token at context ``ctx``; ``prefill_logit_ops(dims)``, those of
  the logits a prefill computes for its last position;
  ``decode_step_least_s(dims, peaks, slots, kv_entries, ctx_sum,
  attn_keep, mlp_keep, lean)``, seconds of one decode step at the
  chip's peaks; ``kept_linears(dims, lean)``, ``(linear, layers)`` for
  each fused-linear call of a layer, with the number of layers whose
  router leans to keeping it; ``linear_call(linear, rows)``, ``(ops,
  bytes)`` of one such call; ``row_share(dims, lean, keep)``, for each
  entry of ``kept_linears`` the share of rows it has to compute;
  ``paged_attention_call(dims, ctx_sum, slots, share)``, ``(ops,
  bytes)`` of one layer's paged-attention call, averaged over the
  layers; ``kv_entry_bytes(dims)``, the payload of one stored (token,
  layer) entry; ``model_weight_bytes(dims, lean)``, the weight bytes a
  decode step reads.

A model whose block is not dense GQA joins the benchmark as new files
only: its family module here; its configuration file under
``configs/``, naming the family; a traffic file under ``traffic/`` where
its mix is new; a reader under ``metrics/`` for each new per-layer
metric; and their entries in ``BENCHMARK.json``.
"""
