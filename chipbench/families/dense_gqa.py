"""Dense grouped-query attention and a SwiGLU MLP in every layer, each
block behind a SkipGPT router: the weights of ``weights.py``, the plain
reference of ``reference.py`` and the counts of ``counts.py``, bound to
the family interface (``families/__init__.py``)."""
from chipbench import counts, reference, weights

dims_of = weights.dims_of
hf_keys = {
    "intermediate_size": "d_ff",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "resolved_head_dim",
    "rope_theta": "rope_theta",
}
program_params = weights.program_params
keep_masks = weights.keep_masks

token_flops = counts.token_flops
decode_step_least_s = counts.decode_step_least_s
kept_linears = counts.kept_linears
linear_call = counts.linear_call
paged_attention_call = counts.paged_attention_call
kv_entry_bytes = counts.kv_entry_bytes
model_weight_bytes = counts.model_weight_bytes


def reference_run(conf, key, seqs, rows, lowp_modes=(False,)):
    return reference.run(conf, key, seqs, rows, lowp_modes), {}


def prefill_logit_ops(dm) -> float:
    """The output head over one position: the logits of a prefill's
    last token."""
    return 2 * dm.d * dm.vocab


def row_share(dm, lean, keep):
    """``counts.row_share`` for each entry of ``kept_linears``: attention's
    two calls, then the MLP's two."""
    att, mlp = counts.row_share(dm, lean, keep)
    return (att, att, mlp, mlp)
