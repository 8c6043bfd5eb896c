"""BERT / ERNIE transformer encoder + pretraining program.

BASELINE configs 3 (BERT-base) and 4 (ERNIE-large — the north-star
data-parallel workload). The reference era trains these via PaddleNLP model
zoos on the fluid layers API; here the encoder is built the same way
(program IR), with TPU-native extras:

* bf16-friendly compute (layer_norm/softmax accumulate in fp32),
* Megatron-style tensor-parallel sharding annotations on the QKV/FFN weights
  (parallel/api.shard_tensor) — GSPMD emits the allreduces the reference
  lacked first-class TP for (SURVEY.md §2.7),
* batch axis sharded over 'dp', sequence shardable over 'sp'.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass

import numpy as np

from .. import layers
from ..core.ir import Program, program_guard
from ..initializer import Normal, TruncatedNormal
from ..param_attr import ParamAttr
from ..parallel.api import set_logical_axes, shard_tensor


@dataclass
class BertConfig:
    vocab_size: int = 30522
    hidden_size: int = 768
    num_hidden_layers: int = 12
    num_attention_heads: int = 12
    intermediate_size: int = 3072
    max_position_embeddings: int = 512
    type_vocab_size: int = 2
    hidden_dropout_prob: float = 0.1
    attention_probs_dropout_prob: float = 0.1
    initializer_range: float = 0.02
    hidden_act: str = "gelu"
    dtype: str = "float32"
    # emit the fused Pallas flash-attention op instead of the
    # matmul/softmax/matmul chain (ops/attention_ops.py). Probability
    # dropout is folded away on this path (flash kernels don't
    # materialise probs); hidden dropout is unaffected.
    use_flash_attention: bool = False
    # emit ring_attention ops (parallel/ring_attention.py): the sequence
    # axis is sharded over the 'sp' mesh axis and kv shards rotate over
    # ICI. Set by build_pretraining_program(sequence_parallel=n).
    use_ring_attention: bool = False


def bert_base() -> BertConfig:
    return BertConfig()


def bert_large() -> BertConfig:
    return BertConfig(hidden_size=1024, num_hidden_layers=24,
                      num_attention_heads=16, intermediate_size=4096)


def ernie_large() -> BertConfig:
    """ERNIE 2.0 large (Baidu flagship): BERT-large geometry, 18k vocab
    (reference-era ERNIE uses its own WordPiece vocab)."""
    return BertConfig(vocab_size=18000, hidden_size=1024, num_hidden_layers=24,
                      num_attention_heads=16, intermediate_size=4096)


def _param(name, cfg):
    return ParamAttr(name=name, initializer=TruncatedNormal(
        0.0, cfg.initializer_range))


def _allreduce_sum(x, axes, nranks):
    """Append an in-program c_allreduce_sum over mesh `axes` (multi-axis
    psum; ops/collective_ops.py)."""
    from ..layer_helper import LayerHelper

    helper = LayerHelper("c_allreduce_sum")
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("c_allreduce_sum", {"X": [x]}, {"Out": [out]},
                     {"axis_name": list(axes), "nranks": nranks})
    return out


def _dense(x, d_out, name, cfg, act=None, tp_spec=None):
    """3-D dense: [B,S,H] @ [H,d_out] + b, with optional TP sharding spec on
    the weight (e.g. (None,'mp') column-parallel, ('mp',None) row-parallel)."""
    w = layers.create_parameter([int(x.shape[-1]), d_out], cfg.dtype,
                                attr=_param(name + "_w", cfg))
    if tp_spec is not None:
        shard_tensor(w, tp_spec)
    else:
        # declarative tier: the rule table maps ("embed","mlp") to mesh
        # axes (parallel/axis_rules.py); explicit tp_spec overrides
        set_logical_axes(w, ("embed", "mlp"))
    b = layers.create_parameter([d_out], cfg.dtype,
                                attr=ParamAttr(name=name + "_b"), is_bias=True)
    if tp_spec is not None and tp_spec[-1] is not None:
        shard_tensor(b, (tp_spec[-1],))
    elif tp_spec is None:
        set_logical_axes(b, ("mlp",))
    out = layers.linear(x, w, b)
    if act == "gelu":
        out = layers.gelu(out, approximate=True)
    elif act:
        out = getattr(layers, act)(out)
    return out


def _attention(x, attn_bias, cfg: BertConfig, name: str, is_test=False,
               attn_bias2d=None):
    """Multi-head self-attention via program ops (matmul/reshape/transpose/
    softmax). Swappable with the fused flash-attention op (ops/attention_ops)
    by the fuse pass; QKV is column-parallel, the output projection
    row-parallel (Megatron pattern)."""
    h = cfg.hidden_size
    n = cfg.num_attention_heads
    hd = h // n
    # Three separate projections instead of one stacked 3h matmul +
    # slice/squeeze of the [3,B,n,S,hd] transpose: the stacked form
    # materialised the full 5-D transpose and then paid three strided
    # slice copies per layer fwd AND bwd (~30 ms/step measured on the
    # b34 ERNIE profile, BASELINE.md); with per-projection
    # outputs XLA folds each [B,S,n,hd]->[B,n,S,hd] transpose into the
    # dot's output layout. Same Megatron column-parallel sharding.
    if cfg.use_flash_attention and not cfg.use_ring_attention:
        # PACKED path: the projections' [B,S,H] outputs feed the fused
        # kernels directly (layers.flash_attention num_heads=) and ctx
        # comes back [B,S,H] — zero reshape/transpose ops per layer
        # (~13.9 ms/step of head transposes in the round-4 profile)
        q3 = _dense(x, h, f"{name}_q", cfg, tp_spec=(None, "mp"))
        k3 = _dense(x, h, f"{name}_k", cfg, tp_spec=(None, "mp"))
        v3 = _dense(x, h, f"{name}_v", cfg, tp_spec=(None, "mp"))
        ctx = layers.flash_attention(
            q3, k3, v3, bias=attn_bias, scale=1.0 / np.sqrt(hd),
            num_heads=n, dropout_rate=cfg.attention_probs_dropout_prob,
            is_test=is_test)
        return _dense(ctx, h, f"{name}_out", cfg, tp_spec=("mp", None))

    def proj(suffix):
        t = _dense(x, h, f"{name}_{suffix}", cfg, tp_spec=(None, "mp"))
        t = layers.reshape(t, [0, 0, n, hd])
        return layers.transpose(t, [0, 2, 1, 3])      # [B,n,S,hd]

    q, k, v = proj("q"), proj("k"), proj("v")
    if cfg.use_ring_attention:
        ctx = layers.ring_attention(
            q, k, v, bias=attn_bias2d, scale=1.0 / np.sqrt(hd),
            axis_name="sp",
            dropout_rate=cfg.attention_probs_dropout_prob, is_test=is_test)
    else:
        scores = layers.matmul(q, k, transpose_y=True, alpha=1.0 / np.sqrt(hd))
        if attn_bias is not None:
            scores = scores + attn_bias
        probs = layers.softmax(scores)
        probs = layers.dropout(probs, cfg.attention_probs_dropout_prob,
                               is_test=is_test,
                               dropout_implementation="upscale_in_train")
        ctx = layers.matmul(probs, v)                 # [B,n,S,hd]
    ctx = layers.transpose(ctx, [0, 2, 1, 3])
    ctx = layers.reshape(ctx, [0, 0, h])
    return _dense(ctx, h, f"{name}_out", cfg, tp_spec=("mp", None))


def _encoder_layer(x, attn_bias, cfg: BertConfig, name: str, is_test=False,
                   attn_bias2d=None):
    attn = _attention(x, attn_bias, cfg, f"{name}_attn", is_test,
                      attn_bias2d=attn_bias2d)
    attn = layers.dropout(attn, cfg.hidden_dropout_prob, is_test=is_test,
                          dropout_implementation="upscale_in_train")
    x = layers.layer_norm(x + attn, begin_norm_axis=2,
                          param_attr=ParamAttr(name=f"{name}_ln1_scale"),
                          bias_attr=ParamAttr(name=f"{name}_ln1_bias"))
    ffn = _dense(x, cfg.intermediate_size, f"{name}_ffn1", cfg,
                 act=cfg.hidden_act, tp_spec=(None, "mp"))
    ffn = _dense(ffn, cfg.hidden_size, f"{name}_ffn2", cfg,
                 tp_spec=("mp", None))
    ffn = layers.dropout(ffn, cfg.hidden_dropout_prob, is_test=is_test,
                         dropout_implementation="upscale_in_train")
    return layers.layer_norm(x + ffn, begin_norm_axis=2,
                             param_attr=ParamAttr(name=f"{name}_ln2_scale"),
                             bias_attr=ParamAttr(name=f"{name}_ln2_bias"))


def _attn_bias_from_mask(input_mask):
    """Additive attention bias from the [B,S] 0/1 mask:
    (mask-1)*1e4 → 0 on real tokens, -1e4 on padding. Kept 2-D for the
    ring-attention path (the bias shard travels with its kv shard) and
    unsqueezed to [B,1,1,S] for the dense paths."""
    bias2d = layers.scale(input_mask, scale=10000.0, bias=-1.0,
                          bias_after_scale=False)
    bias2d.stop_gradient = True
    attn_bias = layers.unsqueeze(bias2d, [1, 2])
    attn_bias.stop_gradient = True
    return attn_bias, bias2d


def bert_encoder(src_ids, sent_ids, pos_ids, input_mask, cfg: BertConfig,
                 is_test=False, pipeline_stages: int = 0):
    """Token+segment+position embeddings → N transformer layers.
    Returns sequence output [B,S,H].

    pipeline_stages=p (>1) tags op groups with device_guard("stage:k") for
    the PipelineOptimizer: embeddings + the first layer block on stage 0,
    then ceil(L/p) layers per stage. The attention bias is re-derived from
    the input_mask feed inside every stage (feeds are visible to all
    stages; cross-stage dataflow is restricted to k→k+1)."""
    from ..core.ir import device_guard

    p = int(pipeline_stages or 0)
    if p > 1 and p > cfg.num_hidden_layers:
        raise ValueError(
            f"pipeline_stages={p} exceeds num_hidden_layers="
            f"{cfg.num_hidden_layers} — some stages would be empty")
    if p > 1:
        # balanced partition: L//p per stage, first L%p stages get one extra
        base, rem = divmod(cfg.num_hidden_layers, p)
        bounds = []
        acc = 0
        for k in range(p):
            acc += base + (1 if k < rem else 0)
            bounds.append(acc)

    def stage_of_layer(i):
        if p <= 1:
            return None
        for k, b in enumerate(bounds):
            if i < b:
                return "stage:%d" % k
        return "stage:%d" % (p - 1)

    with device_guard("stage:0" if p > 1 else None):
        emb = layers.embedding(src_ids, [cfg.vocab_size, cfg.hidden_size],
                               param_attr=_param("word_embedding", cfg),
                               dtype=cfg.dtype)
        semb = layers.embedding(sent_ids,
                                [cfg.type_vocab_size, cfg.hidden_size],
                                param_attr=_param("sent_embedding", cfg),
                                dtype=cfg.dtype)
        pemb = layers.embedding(pos_ids, [cfg.max_position_embeddings,
                                          cfg.hidden_size],
                                param_attr=_param("pos_embedding", cfg),
                                dtype=cfg.dtype)
        x = emb + semb + pemb
        x = layers.layer_norm(x, begin_norm_axis=2,
                              param_attr=ParamAttr(name="emb_ln_scale"),
                              bias_attr=ParamAttr(name="emb_ln_bias"))
        x = layers.dropout(x, cfg.hidden_dropout_prob, is_test=is_test,
                           dropout_implementation="upscale_in_train")
        attn_bias, bias2d = _attn_bias_from_mask(input_mask)
    cur_stage = "stage:0"
    for i in range(cfg.num_hidden_layers):
        stage = stage_of_layer(i)
        with device_guard(stage):
            if stage is not None and stage != cur_stage:
                # new stage: re-derive the bias from the feed so the only
                # cross-stage tensor is x
                attn_bias, bias2d = _attn_bias_from_mask(input_mask)
                cur_stage = stage
            x = _encoder_layer(x, attn_bias, cfg, f"layer_{i}", is_test,
                               attn_bias2d=bias2d)
    return x


def build_pretraining_program(cfg: BertConfig, seq_len: int = 128,
                              batch_size: int = -1, optimizer_name="adamw",
                              lr: float = 1e-4, is_test=False,
                              with_optimizer=True, with_nsp=True,
                              sequence_parallel: int = 0,
                              data_parallel: int = 1,
                              pipeline_stages: int = 0,
                              num_microbatches: int = 1,
                              max_predictions_per_seq: int = 0,
                              pipeline_schedule: str = "gpipe"):
    """MLM + NSP pretraining step (the reference-era BERT/ERNIE recipe).

    Feeds: src_ids, sent_ids, pos_ids, input_mask [B,S];
           mask_labels [B,S] int64 (-0 where unmasked), mask_pos_weight [B,S]
           float 1.0 at masked positions; nsp_labels [B,1].
    seq_len must fit the position table — an out-of-range position
    gather would train on garbage rows (found as a NaN loss at
    seq 2048 with the default 512-entry table).
    Fetches: loss (total), lm_loss, nsp_loss (0 when with_nsp=False).

    sequence_parallel=n (>1) builds the long-context SP variant: ring
    attention over the 'sp' mesh axis, token feeds sharded ('dp','sp'),
    MLM loss globally normalised via in-program c_allreduce_sum, grads
    summed (not averaged) over ('dp','sp'). NSP is dropped on this path
    (its [CLS] pooling is not sequence-shardable).

    pipeline_stages=p (>1) builds the pipeline-parallel variant: encoder
    layers tagged over p device_guard stages, optimizer wrapped in
    PipelineOptimizer(num_microbatches) — the forward becomes one GPipe
    schedule op over the 'pp' mesh axis. Only `loss` is fetchable on this
    path (stage intermediates live inside the schedule). Mutually
    exclusive with sequence_parallel for now. Loss semantics on this path
    are gradient-accumulation style — the MEAN of per-microbatch
    sum(loss*w)/sum(w) ratios — which differs from the dense program's
    global masked-token mean when masked counts vary across microbatches
    (same trade the reference's GradientMergeOptimizer makes,
    optimizer.py:5025).
    """
    if seq_len > cfg.max_position_embeddings:
        raise ValueError(
            f"seq_len {seq_len} exceeds max_position_embeddings "
            f"{cfg.max_position_embeddings} — raise the config's table "
            f"size for long-context runs")
    pp = int(pipeline_stages or 0)
    sp = int(sequence_parallel or 0)
    dp = int(data_parallel or 1)
    if pp > 1 and sp > 1:
        if cfg.num_hidden_layers % pp:
            # composed SP x PP requires equal ring-attention collective
            # counts in every lax.switch branch (stage) — see
            # optimizer/pipeline.py post-op design
            raise ValueError(
                f"sequence_parallel with pipeline_stages needs "
                f"num_hidden_layers ({cfg.num_hidden_layers}) divisible by "
                f"pipeline_stages ({pp}) so stages are collective-uniform")
    if sp > 1:
        cfg = dataclasses.replace(cfg, use_ring_attention=True)
        with_nsp = False
    main, startup = Program(), Program()
    with program_guard(main, startup):
        B, S = batch_size, seq_len
        src_ids = layers.static_data("src_ids", [B, S], "int64")
        sent_ids = layers.static_data("sent_ids", [B, S], "int64")
        pos_ids = layers.static_data("pos_ids", [B, S], "int64")
        input_mask = layers.static_data("input_mask", [B, S], "float32")
        mask_labels = layers.static_data("mask_labels", [B, S], "int64")
        mask_weight = layers.static_data("mask_weight", [B, S], "float32")
        nsp_labels = layers.static_data("nsp_labels", [B, 1], "int64")

        seq_out = bert_encoder(src_ids, sent_ids, pos_ids, input_mask, cfg,
                               is_test=is_test, pipeline_stages=pp)

        # MLM head: transform + tied decoder over the word embedding.
        # With max_predictions_per_seq=k, only the top-k masked positions
        # per example are gathered BEFORE the vocab projection — the
        # standard BERT recipe, cutting the [B,S,V] logits (the largest
        # activation) and its matmul to [B,k,V] (~5x at 15% masking).
        # Under SP the gather runs PER SEQUENCE SHARD with
        # k_local = min(k, S/sp): a shard cannot hold more than
        # min(k, S_local) masked positions, so per-shard top-k followed by
        # the global num/denom psum is loss-exact.
        k = int(max_predictions_per_seq or 0)
        if k > 0 and sp > 1:
            k = min(k, seq_len // sp)
        if k > 0:
            w_sel, pos = layers.topk(mask_weight, k)         # [B,k]
            lab_sel = layers.take_along_axis(mask_labels, pos, axis=1)
            pos3 = layers.unsqueeze(pos, [2])                # [B,k,1]
            mlm_in = layers.take_along_axis(seq_out, pos3, axis=1)
            mlm_labels, mlm_weight = lab_sel, w_sel
        else:
            mlm_in, mlm_labels, mlm_weight = seq_out, mask_labels, mask_weight
        trans = _dense(mlm_in, cfg.hidden_size, "mlm_trans", cfg,
                       act=cfg.hidden_act)
        trans = layers.layer_norm(trans, begin_norm_axis=2,
                                  param_attr=ParamAttr(name="mlm_ln_scale"),
                                  bias_attr=ParamAttr(name="mlm_ln_bias"))
        word_emb = main.global_block().var("word_embedding")
        lm_logits = layers.matmul(trans, word_emb, transpose_y=True)
        lm_bias = layers.create_parameter([cfg.vocab_size], cfg.dtype,
                                          attr=ParamAttr(name="mlm_out_bias"),
                                          is_bias=True)
        lm_logits = layers.elementwise_add(lm_logits, lm_bias, axis=-1)
        lm_loss_all = layers.softmax_with_cross_entropy(
            lm_logits, layers.unsqueeze(mlm_labels, [2]))
        lm_loss_all = layers.squeeze(lm_loss_all, [2])
        num = layers.reduce_sum(lm_loss_all * mlm_weight)
        denom = layers.reduce_sum(mlm_weight)
        if sp > 1 and pp > 1:
            # composed SP x PP: the cross-shard psums may NOT live inside
            # a pipeline stage (lax.switch branches must be
            # collective-uniform), so normalisation happens in
            # device_guard("post") ops that the PipelineOptimizer keeps
            # OUTSIDE the schedule op, operating on microbatch-summed
            # num/denom — exact global masked-token mean
            from ..core.ir import device_guard

            with device_guard("post"):
                num = _allreduce_sum(num, ("dp", "sp"), nranks=sp * dp)
                denom = _allreduce_sum(denom, ("dp", "sp"), nranks=sp * dp)
                lm_loss = num / (denom + 1e-5)
        elif sp > 1:
            # global normalisation: per-shard token sums → psum over the
            # data+sequence shards, so every rank computes the SAME global
            # loss (grads then SUM unscaled — see insert_grad_allreduce)
            num = _allreduce_sum(num, ("dp", "sp"), nranks=sp * dp)
            denom = _allreduce_sum(denom, ("dp", "sp"), nranks=sp * dp)
            lm_loss = num / (denom + 1e-5)
        else:
            lm_loss = num / (denom + 1e-5)

        if with_nsp:
            # NSP head on pooled [CLS]
            first_tok = layers.slice(seq_out, [1], [0], [1])
            pooled = _dense(first_tok, cfg.hidden_size, "pooler", cfg,
                            act="tanh")
            pooled = layers.reshape(pooled, [0, cfg.hidden_size])
            nsp_logits = layers.fc(pooled, 2, param_attr=_param("nsp_w", cfg),
                                   bias_attr=ParamAttr(name="nsp_b"))
            nsp_loss = layers.mean(
                layers.softmax_with_cross_entropy(nsp_logits, nsp_labels))
            loss = lm_loss + nsp_loss
        elif sp > 1 and pp > 1:
            from ..core.ir import device_guard

            with device_guard("post"):
                nsp_loss = layers.fill_constant([1], "float32", 0.0)
            loss = lm_loss
        else:
            nsp_loss = layers.fill_constant([1], "float32", 0.0)
            loss = lm_loss

        if with_optimizer:
            from .. import optimizer as opt_mod

            if optimizer_name == "adamw":
                opt = opt_mod.AdamWOptimizer(lr, weight_decay=0.01)
            elif optimizer_name == "lamb":
                opt = opt_mod.LambOptimizer(lr)
            else:
                opt = opt_mod.AdamOptimizer(lr)
            if sp > 1 and pp > 1:
                # composed dp x sp x pp: the pipeline op accumulates
                # num/denom, post ops psum them over (dp, sp), and grads
                # SUM over all three axes (globally-normalised loss)
                from ..optimizer.pipeline import PipelineOptimizer

                if pipeline_schedule != "gpipe":
                    raise ValueError(
                        "sequence_parallel + pipeline_stages requires the "
                        "gpipe schedule (1f1b cannot host the post-op loss "
                        "normalisation — its grads are computed inside the "
                        "schedule op)")
                PipelineOptimizer(
                    opt, num_microbatches=num_microbatches,
                    schedule=pipeline_schedule,
                    grad_axes=("dp", "sp", "pp"),
                    grad_nranks=dp * sp * pp).minimize(loss)
            elif sp > 1:
                # backward → grad allreduce → update (the executor runs ops
                # in block order, so the allreduce MUST precede the
                # optimizer ops — same order fleet_base uses)
                from ..distributed.fleet.meta_optimizers import \
                    insert_grad_allreduce

                params_grads = opt.backward(loss)
                insert_grad_allreduce(main, params_grads, nranks=sp * dp,
                                      axis_name=("dp", "sp"), average=False)
                opt.apply_gradients(params_grads)
            elif pp > 1:
                from ..optimizer.pipeline import PipelineOptimizer

                PipelineOptimizer(opt, num_microbatches=num_microbatches,
                                  schedule=pipeline_schedule).minimize(loss)
            else:
                opt.minimize(loss)

    if sp > 1:
        from ..parallel.api import shard_tensor

        for v in (src_ids, sent_ids, pos_ids, input_mask, mask_labels,
                  mask_weight):
            shard_tensor(v, ("dp", "sp"))

    feeds = dict(src_ids=src_ids, sent_ids=sent_ids, pos_ids=pos_ids,
                 input_mask=input_mask, mask_labels=mask_labels,
                 mask_weight=mask_weight, nsp_labels=nsp_labels)
    fetches = dict(loss=loss, lm_loss=lm_loss, nsp_loss=nsp_loss)
    return main, startup, feeds, fetches


def synthetic_pretraining_batch(cfg: BertConfig, batch_size: int, seq_len: int,
                                seed: int = 0,
                                max_predictions_per_seq: int = 0):
    """max_predictions_per_seq caps the masked count per row (the standard
    BERT data-pipeline contract — required for the masked-gather MLM head
    to be loss-exact)."""
    rng = np.random.RandomState(seed)
    src = rng.randint(0, cfg.vocab_size, (batch_size, seq_len)).astype(np.int64)
    sent = rng.randint(0, cfg.type_vocab_size,
                       (batch_size, seq_len)).astype(np.int64)
    pos = np.tile(np.arange(seq_len, dtype=np.int64), (batch_size, 1))
    mask = np.ones((batch_size, seq_len), np.float32)
    labels = rng.randint(0, cfg.vocab_size, (batch_size, seq_len)).astype(np.int64)
    weight = (rng.rand(batch_size, seq_len) < 0.15).astype(np.float32)
    k = int(max_predictions_per_seq or 0)
    if k > 0:
        for row in weight:      # keep only the first k masked positions
            hits = np.flatnonzero(row)
            if len(hits) > k:
                row[hits[k:]] = 0.0
    nsp = rng.randint(0, 2, (batch_size, 1)).astype(np.int64)
    return dict(src_ids=src, sent_ids=sent, pos_ids=pos, input_mask=mask,
                mask_labels=labels, mask_weight=weight, nsp_labels=nsp)
