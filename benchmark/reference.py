"""Plain references, independent of the code under test.

decoder: the forward pass of the `decoder_lm` block as its programs define
it (models/decoder_lm.py), written in straightforward `jax.numpy` float32:
no KV cache, no paging, no kernels, no batching. Token embedding scaled by
sqrt(d_model) plus a sinusoidal position table; per layer multi-head causal
attention, post-layer-norm residuals, a ReLU feed-forward; logits through
the tied embedding. Departures from the published XGLM block are listed in
configs/xglm_1p7b.json.

The comparison is by logits and a margin, not by token equality: with
random weights the largest logit turns on rounding. The engine's greedy
tokens are teacher-forced through the reference, and every token the engine
chose must have a reference logit within MARGIN of the reference's maximum
at that position.

MARGIN: logits here are about unit scale (layer-normed activations against
embedding rows of std d_model^-0.5): the largest of 256k is about 4.5-5 and
the runner-up usually 0.2 under it. With random weights the two best tokens
of some prompts lie within a few hundredths of each other at every
position, and the float32 engine, which multiplies at the TPU's default
precision, then picks the other one: on the chip 3 of 60 checked prompts did
so, at gaps of 0.016, 0.016 and 0.029, and the other 57 agreed with the
reference's argmax at all 8 positions (PERF.md, PR 23). 0.1 is about three
times the worst gap read and half the usual distance to the runner-up; a
wrong position, a stale page or a missing layer is off by the whole logit
scale. It does not tell float32 from bfloat16 weights: that needs the
engine's logits, which it does not give out.

bert: the MLM + NSP pretraining loss of models/bert.py with dropout off,
and its gradients by `jax.grad`; see `check_train_step`.
"""

from __future__ import annotations

MARGIN = 0.1
LN_EPS = 1e-5


def _layer_norm(x, scale, bias):
    import jax.numpy as jnp

    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LN_EPS) * scale + bias


def decoder_logits(params, tokens, n_layers: int, n_head: int,
                   first=0, rows: int = 0):
    """[T] token ids -> float32 logits, causal, no cache: of every position,
    or of the `rows` positions from `first` on (the hidden states are the
    same; only the projection onto the vocabulary is cut)."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        emb = params["lm_tok_emb"]
        d = emb.shape[1]
        hd = d // n_head
        t = tokens.shape[0]
        x = emb[tokens] * (d ** 0.5) + params["lm_pos_enc"][:t]
        causal = jnp.tril(jnp.ones((t, t), bool))
        for i in range(n_layers):
            p = f"lm_l{i}_"

            def dense(v, name):
                return v @ params[p + name + "_w"] + params[p + name + "_b"]

            q = dense(x, "q").reshape(t, n_head, hd)
            k = dense(x, "k").reshape(t, n_head, hd)
            v = dense(x, "v").reshape(t, n_head, hd)
            scores = jnp.einsum("qnh,knh->nqk", q, k) * (hd ** -0.5)
            scores = jnp.where(causal[None], scores, -jnp.inf)
            ctx = jnp.einsum("nqk,knh->qnh", jax.nn.softmax(scores, axis=-1),
                             v).reshape(t, d)
            x = _layer_norm(dense(ctx, "o") + x, params[p + "ln1_scale"],
                            params[p + "ln1_bias"])
            h = jax.nn.relu(dense(x, "fc1"))
            x = _layer_norm(dense(h, "fc2") + x, params[p + "ln2_scale"],
                            params[p + "ln2_bias"])
        if rows:
            x = jax.lax.dynamic_slice_in_dim(x, first, rows)
        return x @ emb.T


def check_greedy(params, n_layers: int, n_head: int, prompt, chosen,
                 pad_to: int, margin: float = MARGIN):
    """Teacher-force prompt + the engine's greedy tokens through the
    reference. Returns (ok, worst_gap, gaps): a gap is the distance, at one
    generated position, between the reference's maximum logit and the
    reference's logit of the token the engine chose (0 = same argmax).

    The sequence is right-padded to `pad_to` so that every check of a run
    shares one compiled program; causal attention keeps the padding from
    touching the positions that are read."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    prompt = np.asarray(prompt, np.int32).reshape(-1)
    chosen = np.asarray(chosen, np.int32).reshape(-1)
    seq = np.concatenate([prompt, chosen])
    if seq.size > pad_to:
        raise ValueError(f"sequence of {seq.size} tokens over pad_to {pad_to}")
    padded = np.zeros(pad_to, np.int32)
    padded[:seq.size] = seq
    fn = jax.jit(decoder_logits, static_argnums=(2, 3, 5))
    # position L-1+j predicts generated token j
    rows = np.asarray(fn(params, jnp.asarray(padded), n_layers, n_head,
                         prompt.size - 1, chosen.size))
    gaps = rows.max(axis=1) - rows[np.arange(chosen.size), chosen]
    worst = float(gaps.max())
    return worst <= margin, worst, [round(float(g), 5) for g in gaps]


# ---------------------------------------------------------------------------
# training

def bert_pretraining_loss(params, batch, n_layers: int, n_head: int):
    """The MLM + NSP loss of models/bert.py's pretraining program with every
    dropout off, in plain float32 `jax.numpy` at "highest" precision: no
    Program, no Executor, no kernels, no gather of the masked positions
    (an unmasked position's weight is 0, so the weighted mean over all
    positions is the same number). `params` are the program's parameters by
    name, `batch` the seven feeds of generators/train_ring.batch."""
    import jax
    import jax.numpy as jnp

    def dense(x, name):
        return x @ params[name + "_w"] + params[name + "_b"]

    def norm(x, name):
        return _layer_norm(x, params[name + "_scale"], params[name + "_bias"])

    def gelu(x):
        return jax.nn.gelu(x, approximate=True)

    def mean_xent(logits, labels, weight=None):
        picked = jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1),
                                     labels[..., None], axis=-1)[..., 0]
        if weight is None:
            return -jnp.mean(picked)
        return -jnp.sum(picked * weight) / (jnp.sum(weight) + 1e-5)

    with jax.default_matmul_precision("highest"):
        word = params["word_embedding"]
        b, s = batch["src_ids"].shape
        hd = word.shape[1] // n_head
        x = norm(word[batch["src_ids"]]
                 + params["sent_embedding"][batch["sent_ids"]]
                 + params["pos_embedding"][batch["pos_ids"]], "emb_ln")
        bias = (batch["input_mask"] - 1.0)[:, None, None, :] * 1e4
        for i in range(n_layers):
            p = f"layer_{i}_"
            q, k, v = (dense(x, p + "attn_" + n).reshape(b, s, n_head, hd)
                       for n in "qkv")
            scores = jnp.einsum("bqnh,bknh->bnqk", q, k) * (hd ** -0.5)
            ctx = jnp.einsum("bnqk,bknh->bqnh",
                             jax.nn.softmax(scores + bias, axis=-1), v)
            x = norm(x + dense(ctx.reshape(b, s, -1), p + "attn_out"),
                     p + "ln1")
            x = norm(x + dense(gelu(dense(x, p + "ffn1")), p + "ffn2"),
                     p + "ln2")
        trans = norm(gelu(dense(x, "mlm_trans")), "mlm_ln")
        lm = mean_xent(trans @ word.T + params["mlm_out_bias"],
                       batch["mask_labels"], batch["mask_weight"])
        pooled = jnp.tanh(dense(x[:, 0], "pooler"))
        nsp = mean_xent(pooled @ params["nsp_w"] + params["nsp_b"],
                        batch["nsp_labels"][:, 0])
        return lm + nsp


def check_train_step(params, batch, n_layers: int, n_head: int, loss,
                     grads: dict):
    """Holds one step of the Executor (dropout off) against the reference:
    its loss, and the gradients it fetched for the parameters named in
    `grads`. Returns (notes, facts): notes is empty when all agree.

    A gradient is compared by the relative L2 error |g - g_ref| / |g_ref|
    over the whole tensor: the first layer's depend on every layer above
    them and on the whole backward pass, so a layer left out, a wrong mask
    or a cheaper number format moves them, where the loss of a freshly
    initialised model (the entropy of a uniform guess, whatever the
    network computes) hardly moves at all."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    as32 = {n: jnp.asarray(v, jnp.float32) for n, v in params.items()}
    feeds = {n: jnp.asarray(v, jnp.float32 if v.dtype.kind == "f"
                            else jnp.int32) for n, v in batch.items()}
    fn = jax.jit(jax.value_and_grad(bert_pretraining_loss),
                 static_argnums=(2, 3))
    ref_loss, ref_grads = fn(as32, feeds, n_layers, n_head)
    ref_loss = float(ref_loss)
    facts = {"loss": float(loss), "reference_loss": ref_loss,
             "grad_rel_err": {}}
    notes = []
    if not abs(float(loss) - ref_loss) <= LOSS_TOL * abs(ref_loss):
        notes.append(f"step loss {float(loss)} against the reference's "
                     f"{ref_loss}: over {LOSS_TOL} relative")
    for name, g in grads.items():
        want = np.asarray(ref_grads[name], np.float64)
        got = np.asarray(jnp.asarray(g, jnp.float32), np.float64)
        err = float(np.linalg.norm(got - want) / np.linalg.norm(want))
        facts["grad_rel_err"][name] = err
        if not err <= GRAD_TOL:
            notes.append(f"gradient of {name} is {err:.4f} (relative L2) "
                         f"from the reference's: over {GRAD_TOL}")
    return notes, facts


# Tolerances of check_train_step, for a bfloat16 program against a float32
# reference: about three times the errors read on the chip at ERNIE-large's
# widths, 2 layers, batch 4 x 512 (loss 2.6e-4, gradients 0.57-0.72%;
# PERF.md, PR 23). The Executor at float32 on the CPU agrees to 1e-5 and
# 1e-3 (tests/benchmark_suite).
LOSS_TOL = 1e-3
GRAD_TOL = 0.02
