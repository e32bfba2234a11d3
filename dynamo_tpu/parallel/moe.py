"""Expert-parallel MoE dispatch: capacity-bounded scatter/combine.

TPU-native routed-MoE execution, replacing the dense every-token-through-
every-expert formulation (``models/llama._mlp_moe`` dense path) with the
standard capacity-based dispatch used by TPU MoE stacks (GShard/Switch
lineage), expressed so GSPMD turns the data movement into all-to-all
collectives over the ``ep`` mesh axis:

- Router top-k picks (expert, weight) per token; every (token, choice) pair
  gets a *position* inside its expert's fixed-capacity buffer via a one-hot
  cumsum (O(N*k*E), no vocabulary-scale sorts, static shapes throughout).
- Tokens are **scattered** into ``[E, C, D]`` expert buffers (O(N*k*D) data
  movement — never the O(N*E*C*D) dispatch-einsum of the original GShard
  formulation, which is quadratic in tokens at prefill widths).
- Expert FFNs run as batched matmuls ``[E, C, D] @ [E, D, F]`` — one MXU
  contraction over all local experts. With ``w_gate/w_up/w_down`` sharded
  ``P(None, ep, None, tp)`` (see ``parallel/sharding.py``), GSPMD shards the
  expert axis and inserts the token all-to-all at the scatter/gather
  boundaries; ICI carries exactly the dispatched tokens.
- Combine gathers each choice's output row and mixes by routing weight.

Over-capacity tokens are dropped (zero contribution from that choice,
Switch-style, earlier tokens win); serving engines size ``capacity_factor``
so drops are measure-zero, and tests use a no-drop capacity to prove
bit-parity with the dense formulation.

Parity: the reference delegates wide-EP MoE serving to SGLang's DeepEP path
(`examples/sglang/`, SURVEY.md §2 parallelism table row EP); this module is
the first-party TPU equivalent of that capability.
"""

from __future__ import annotations

import os
import threading

import jax
import numpy as np
import jax.numpy as jnp

from dynamo_tpu.models.quant import maybe_dequant as _dq


class _DropCounter:
    """Process-wide cumulative (choices, drops) across every capacity
    dispatch — the live counterpart of :func:`moe_drop_stats` (which
    recomputes routing offline). Fed from inside the jitted dispatch via
    ``jax.debug.callback`` (two scalars per MoE layer per step, async — no
    device stall), read by ``EngineCore.metrics()`` into
    ``ForwardPassMetrics.moe_*`` and from there onto the Prometheus plane
    (`deploy/metrics_service.py`). Process-wide because the dispatch has no
    engine identity; workers run one engine per process, so per-worker
    series stay exact (a dual-engine test process sees the sum).

    The dropless and dense dispatches never drop, so their zero is implicit.

    Counts are DISPATCH-level: the runner bucket-pads batch/time, and padded
    rows route and occupy capacity slots like real ones, so ``choices``
    includes them. The drop *rate* stays representative because
    :func:`expert_capacity` is sized from the same padded N — padding
    inflates numerator and denominator together, it does not mask real
    drops.
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.choices = 0
        self.dropped = 0

    def add(self, choices: int, dropped: int) -> None:
        with self._lock:
            self.choices += int(choices)
            self.dropped += int(dropped)

    def snapshot(self) -> tuple[int, int]:
        with self._lock:
            return self.choices, self.dropped

    def reset(self) -> None:
        with self._lock:
            self.choices = 0
            self.dropped = 0


DROP_COUNTER = _DropCounter()


def _drop_stats_enabled() -> bool:
    """DYNAMO_MOE_DROP_STATS=0 disables the in-dispatch counter (default on:
    the TPU runtime implements host callbacks — checked on a v5e)."""
    return os.environ.get("DYNAMO_MOE_DROP_STATS", "") != "0"


def route_tokens(
    lp: dict,
    x: jnp.ndarray,  # [N, D] flattened tokens
    *,
    k: int,
    scoring: str = "softmax",
    norm_topk: bool = True,
    scaling: float = 1.0,
    n_group: int = 0,
    topk_group: int = 0,
    group_score: str = "max",
    f32_logits: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Router semantics shared by every MoE family; returns (weights f32[N,k],
    expert ids i32[N,k]).

    ``f32_logits``: the router matmul accumulates and hands over float32 (a
    family whose published router is float32 end to end); otherwise the
    product is rounded to the activations' dtype before the softmax.

    - ``softmax`` scoring + ``norm_topk``: Mixtral (softmax over all logits,
      gather top-k, renormalize — algebraically softmax(top-k logits)).
    - ``softmax`` without norm: Qwen2-MoE (weights are raw softmax probs).
    - ``sigmoid``: DeepSeek-V3. Selection uses scores *plus* the aux-free
      load-balancing bias ``router_bias`` (e_score_correction_bias,
      topk_method=noaux_tc), optionally group-limited: experts are split
      into ``n_group`` groups, only the best ``topk_group`` groups stay
      eligible. The *weights* use the unbiased scores, renormalized, then
      scaled by ``routed_scaling_factor``. (HF `modeling_deepseek_v3.py`.)
    - ``group_score``: how a group is ranked — DeepSeek-V2's
      group_limited_greedy uses the per-group ``"max"`` score
      (`modeling_deepseek_v2.py:76`); V3's noaux_tc uses the ``"top2sum"``
      of biased scores (`modeling_deepseek_v3.py:127`).
    """
    if f32_logits:
        logits = jnp.dot(x, lp["router"], preferred_element_type=jnp.float32)
    else:
        logits = (x @ lp["router"]).astype(jnp.float32)  # [N, E]
    if scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    elif scoring == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError(f"unknown moe scoring {scoring!r}")
    weights, topi = select_experts(
        scores, lp.get("router_bias"), form=router_select(scores.shape[0], scores.shape[1], k),
        k=k, n_group=n_group, topk_group=topk_group, group_score=group_score)
    if norm_topk:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    return weights * scaling, topi


#: Where the router takes its experts by passes of ``max``: from this many
#: outputs, and from this many entries of the step's scores (tokens x outputs)
#: a pass. ``lax.top_k``'s sort grows with the tokens and the passes hardly
#: do; under both lines the sort is as cheap or cheaper. Timed on a v5e at
#: 1-2,048 tokens x 64-768 outputs (``tools/router_select_bench.py``; PERF.md, PR 43).
PASSES_FROM_OUTPUTS = 256
PASSES_FROM_ENTRIES_A_PASS = 2048


def router_select(tokens: int, outputs: int, k: int) -> str:
    """How :func:`route_tokens` takes ``k`` experts for each of ``tokens``
    tokens from a router of ``outputs`` outputs: ``"passes"``, ``k`` passes of
    ``max`` over the row; ``"sort"``, ``lax.top_k``, which this chip lowers to
    a sort of every row, and a gather for the weights; ``""`` without a
    router. Both give the same ids, order and weights bit for bit; only the
    cost differs, by the shape. The one predicate behind both the selection
    and a step's ``router_select`` label (``ModelRunner._dispatch``)."""
    if not outputs:
        return ""
    wide = outputs >= PASSES_FROM_OUTPUTS and tokens * outputs >= PASSES_FROM_ENTRIES_A_PASS * k
    return "passes" if wide else "sort"


def _best_groups_only(choice: jnp.ndarray, n_group: int, topk_group: int, group_score: str) -> jnp.ndarray:
    """``choice`` [N, E] with every expert outside the row's best
    ``topk_group`` of ``n_group`` groups at ``-inf``, without a sort or a
    scatter: what ``top_k`` of the groups' scores keeps, ties to the lower
    group as ``top_k`` breaks them."""
    n, e = choice.shape
    grouped = choice.reshape(n, n_group, e // n_group)
    gscore = grouped.max(-1)  # [N, G]
    if group_score == "top2sum" and e // n_group > 1:
        # The second of a group's top two: the maximum again where two entries hold it, else the
        # largest below it (``top_k(grouped, 2)[0].sum(-1)`` value for value).
        at_max = grouped == gscore[..., None]
        below = jnp.where(at_max, -jnp.inf, grouped).max(-1)
        gscore = gscore + jnp.where(at_max.sum(-1, dtype=jnp.int32) > 1, gscore, below)
    # A group's rank is the number of groups that beat it: a higher score, or the same and a lower index.
    g = jnp.arange(n_group, dtype=jnp.int32)
    mine, other = gscore[:, :, None], gscore[:, None, :]
    beaten_by = (other > mine) | ((other == mine) & (g[None, :] < g[:, None]))  # [N, G, G]
    kept = beaten_by.sum(-1, dtype=jnp.int32) < topk_group
    return jnp.where(kept[:, :, None], grouped, -jnp.inf).reshape(n, e)


def _take_by_passes(choice: jnp.ndarray, scores: jnp.ndarray, k: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``lax.top_k(choice, k)``'s ids (descending, the lower index first among
    equals) and ``scores`` at them, by ``k`` passes over the row: each takes
    the first index of the maximum among the entries not yet taken, and the
    score there as a sum with one non-zero term. The passes run on the floats'
    bits as integers in the floats' order, so that a taken entry can be marked
    by a value no entry has (the least integer): ``-inf`` would not do, the
    group limit writes it, and a row whose finite entries run out still returns
    what ``top_k`` returns. Each pass compiles to one fusion."""
    lane = jnp.arange(choice.shape[-1], dtype=jnp.int32)
    bits = jax.lax.bitcast_convert_type(choice, jnp.int32)
    key = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)  # ordered as the floats are
    ids, weights = [], []
    for _ in range(k):
        first = jnp.argmax(key, axis=-1, keepdims=True).astype(jnp.int32)  # [N, 1]
        hit = lane == first
        key = jnp.where(hit, jnp.iinfo(jnp.int32).min, key)
        ids.append(first)
        weights.append(jnp.where(hit, scores, 0.0).sum(-1, keepdims=True))
    return jnp.concatenate(weights, axis=1), jnp.concatenate(ids, axis=1)


def select_experts(
    scores: jnp.ndarray,  # f32[N, E]
    bias: jnp.ndarray | None,  # f32[E]: added for the choice, never for the weight
    *,
    form: str,  # :func:`router_select`'s word
    k: int,
    n_group: int = 0,
    topk_group: int = 0,
    group_score: str = "max",
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The selection of :func:`route_tokens` alone: ``(scores at the chosen
    experts f32[N, k], expert ids i32[N, k])``, the ids as ``lax.top_k`` of
    the biased, group-limited scores orders them."""
    choice = scores if bias is None else scores + bias
    if n_group > 1 and 0 < topk_group < n_group:
        choice = _best_groups_only(choice, n_group, topk_group, group_score)
    if form == "passes":
        return _take_by_passes(choice, scores, k)
    _, topi = jax.lax.top_k(choice, k)
    return jnp.take_along_axis(scores, topi, axis=1), topi  # [N, k] unbiased


def _widen(lp: dict, dtype) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The three expert matrices as the matmuls take them: quantized weights
    are widened here to the activations' ``dtype``, under a name of their own
    (a no-op on plain weights)."""
    with jax.named_scope("moe.widen"):
        return _dq(lp["w_gate"], dtype), _dq(lp["w_up"], dtype), _dq(lp["w_down"], dtype)


_EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


def _kernel_platform() -> bool:
    """Where the grouped-matmul kernel can run: a TPU backend, or anywhere
    under the Pallas interpreter (``DYNAMO_PALLAS_INTERPRET=1``, CPU tests)."""
    from dynamo_tpu.ops.pallas_paged import interpret_mode

    return jax.default_backend() == "tpu" or interpret_mode()


def experts_path(lp: dict, *, mesh=None) -> str:
    """Which formulation the routed experts of ``lp`` take (one layer's
    params or the stacked layers; only trailing axes are read): ``"fused"``,
    the int8 grouped-matmul kernel (``ops/pallas_moe.py``); ``"widened"``,
    every other routed formulation (``_widen`` + ``ragged_dot`` / capacity
    einsums / dense); ``""`` when ``lp`` has no routed experts.

    The one predicate behind both the dispatch (:func:`moe_mlp_dropless`)
    and the step's ``moe_path`` label (``ModelRunner``). The kernel takes
    int8 leaves (``qw``: the per-output-channel scale commutes with the
    contraction; packed int4's group scales do not) that no mesh axis shards
    (``ep`` splits the expert axis, ``tp`` each expert's columns), under the
    dropless dispatch, on a platform that runs it, at widths that tile.
    Everything else keeps the XLA formulations."""
    if "router" not in lp:
        return ""
    from dynamo_tpu.ops.pallas_moe import supported

    leaves = [lp[name] for name in _EXPERT_LEAVES]
    sharded = mesh is not None and any(int(mesh.shape.get(axis, 1)) > 1 for axis in ("ep", "tp"))
    fused = (
        not sharded
        and os.environ.get("DYNAMO_MOE_DISPATCH", "") not in ("capacity", "dense")
        and all(isinstance(leaf, dict) and "qw" in leaf for leaf in leaves)
        and all(supported(*leaf["qw"].shape[-2:]) for leaf in leaves)
        and _kernel_platform()
    )
    return "fused" if fused else "widened"


def split_expert_stack(layers: dict, *, mesh=None) -> tuple[dict, dict | None]:
    """``(xs, stack)`` for a ``lax.scan`` over stacked MoE layers. On the
    fused path the int8 expert arrays leave the scanned tree: a custom call
    on the scan's slice makes XLA copy the layer's experts first (three
    dynamic-slice fusions of 134 MB each at OLMoE's widths, compiled for a
    v5e), so the kernel takes the whole stack and the layer's index instead
    (:func:`join_expert_stack`). Otherwise ``(layers, None)``."""
    if experts_path(layers, mesh=mesh) != "fused":
        return layers, None
    stack = {name: layers[name]["qw"] for name in _EXPERT_LEAVES}
    rest = {name: {k: v for k, v in layers[name].items() if k != "qw"} for name in _EXPERT_LEAVES}
    return {**layers, **rest}, stack


def join_expert_stack(lp: dict, stack: dict | None, layer: jnp.ndarray) -> dict:
    """One scanned layer's params with the stacked int8 experts put back
    beside their sliced scales, and the layer's index as ``expert_layer``."""
    if stack is None:
        return lp
    leaves = {name: {**lp[name], "qw": stack[name]} for name in _EXPERT_LEAVES}
    return {**lp, **leaves, "expert_layer": layer}


def moe_mlp_dropless(
    lp: dict,
    x: jnp.ndarray,  # [N, D] flattened tokens
    *,
    num_experts_per_token: int,
    routing: dict | None = None,
    valid: jnp.ndarray | None = None,  # bool[N]: padding tokens route nowhere (None: every token is valid)
    mesh=None,
) -> jnp.ndarray:
    """Dropless routed MoE via grouped matmuls: ``lax.ragged_dot``, or for
    int8 experts where :func:`experts_path` says so the Pallas kernel that
    reads them as stored (``ops/pallas_moe.py``).

    Token copies are stable-sorted by expert id (an O(N*k) argsort — token
    count, never vocabulary), expert FFNs run as ragged grouped matmuls with
    per-expert group sizes, and results unsort back. No capacity, no drops:
    output is exact and independent of batch composition — the default
    serving path whenever the expert axis is not sharded (parity with the
    dropless DeepEP-style dispatch the reference gets from SGLang).

    A token that ``valid`` masks (a step's padding) has no copies in the
    grouped matmuls, by the convention :func:`moe_mlp_held` shares: its
    copies sort last, the group sizes leave them out (an expert that only
    padding chose is never visited and its weights are never read), and its
    rows, which no matmul wrote, come out as zeros. A valid token's copies
    keep their order among themselves and every row is its own contraction,
    so its output is bit for bit what it is without the mask.
    """
    n, d = x.shape
    e = lp["router"].shape[-1]
    k = num_experts_per_token

    # The scopes name the stages in the compiled ops' ``op_name`` metadata
    # (what a profile shows instead of ``bitcast_multiply_fusion.N``).
    with jax.named_scope("moe.router"):
        weights, topi = route_tokens(lp, x, k=k, **(routing or {}))
        if valid is not None:
            topi = jnp.where(valid[:, None], topi, e)  # one past the last expert: sorted last, in no group

        flat_e = topi.reshape(-1)  # [N*k]
        order = jnp.argsort(flat_e, stable=True)
        xk = jnp.repeat(x, k, axis=0)[order]  # [N*k, D] grouped by expert
        group_sizes = jnp.bincount(flat_e, length=e).astype(jnp.int32)  # a key past the length is dropped

    if experts_path(lp, mesh=mesh) == "fused":
        from dynamo_tpu.ops.pallas_moe import expert_ffn_int8
        from dynamo_tpu.ops.pallas_paged import interpret_mode

        down = expert_ffn_int8(
            xk, lp["w_gate"], lp["w_up"], lp["w_down"], group_sizes, lp.get("expert_layer"),
            interpret=interpret_mode(),
        )
    else:
        w_gate, w_up, w_down = _widen(lp, x.dtype)
        with jax.named_scope("moe.experts_gate_up"):
            gate = jax.nn.silu(jax.lax.ragged_dot(xk, w_gate, group_sizes))
            up = jax.lax.ragged_dot(xk, w_up, group_sizes)
        with jax.named_scope("moe.experts_down"):
            down = jax.lax.ragged_dot(gate * up, w_down, group_sizes)  # [N*k, D]

    with jax.named_scope("moe.combine"):
        rows = jnp.zeros_like(down).at[order].set(down)  # unsort
        out = (rows.astype(jnp.float32) * weights.reshape(-1)[:, None]).reshape(n, k, d).sum(axis=1)
        if valid is not None:  # rows past the groups were never computed: a select, not a product with 0
            out = jnp.where(valid[:, None], out, 0.0)
        return out.astype(x.dtype)


#: Counters a held-share expert layer returns beside its output, in this order.
HELD_COUNTS = ("moe_choices", "moe_choices_zero", "moe_choices_held", "moe_experts_touched", "moe_extra_passes")

#: Token copies (tokens x choices) up to which a held-share layer lays its
#: copies out by dense arithmetic on one-hots (a decode or a mixed step: a few
#: fused operations whose work grows with the square of the copies); beyond
#: (a prefill-sized call) by a sort, a gather and a scatter-add. The largest
#: size timed on a v5e: 128 tokens x 12 choices (PERF.md, PR 39).
DENSE_COPIES = 1536


def held_rows_cap(copies: int, held: int, outputs: int) -> int:
    """Rows the usual pass of the held experts takes: twice the copies even
    routing sends here, in whole 128-row tiles, and never more than there are."""
    even = -(-2 * copies * held // max(1, outputs))
    return min(-(-copies // 16) * 16, -(-max(even, 1) // 128) * 128)


def moe_mlp_held(
    lp: dict,
    x: jnp.ndarray,  # [N, D] flattened tokens
    *,
    num_experts_per_token: int,
    first: int,  # id of the first expert held here
    routed: int,  # routed experts the router scores; outputs past them are identities
    routing: dict | None = None,
    valid: jnp.ndarray | None = None,  # bool[N]: padding tokens route nowhere
    mesh=None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The part of a routed MoE layer that one holder of experts computes:
    ``lp``'s expert arrays hold experts ``[first, first + held)`` of the
    ``routed`` the router scores; its outputs ``>= routed`` are identity
    ("zero-compute") experts. Every token is routed over all outputs; the
    result is the held experts' terms plus the identity term (the token times
    the summed weights of its identity choices), which is computed where the
    token lives. A choice that lands on an expert held elsewhere adds nothing
    here, costs no work and reads no weight: the holders' parts, with the
    identity term counted once, sum to the whole layer.

    A copy's row is its place in the stable order by held expert (copies held
    elsewhere last), so the experts see their copies grouped, through the int8
    kernel where :func:`experts_path` says so. One pass takes the first
    ``held_rows_cap`` rows; where more copies landed here than that (routing
    far from even) the other arm of one ``cond`` takes them all: no loop, and
    exact either way. Up to :data:`DENSE_COPIES` copies the rows are found
    without a sort (a copy's row is the number of copies before it in that
    order: one comparison of every pair), filled by a 0/1 product and added
    back by a product with the weights in float32 (``HIGHEST``: the terms and
    their sums as a float32 scatter-add has them); beyond, by ``argsort``,
    gather and scatter-add. Returns ``(out [N, D], counts i32[5])``, the
    counts as :data:`HELD_COUNTS` names them, over ``valid`` tokens; the last
    is 1 where the pass over every copy's rows ran.

    A token that ``valid`` masks (a step's padding) routes nowhere, by the
    convention :func:`moe_mlp_dropless` shares: its copies sort last (with
    those held elsewhere), stand in no expert's size, and the rows past the
    sized ones, which no matmul wrote, are zeroed in the combine.
    """
    n, d = x.shape
    k = num_experts_per_token
    copies = n * k
    held = jax.tree.leaves(lp["w_gate"])[0].shape[-3]
    fused = experts_path(lp, mesh=mesh) == "fused"
    dense = copies <= DENSE_COPIES
    if valid is None:
        valid = jnp.ones((n,), bool)

    with jax.named_scope("moe.router"):
        weights, topi = route_tokens(lp, x, k=k, f32_logits=True, **(routing or {}))
        local = topi - first
        here = (local >= 0) & (local < held) & valid[:, None]  # [N, k]
        key = jnp.where(here, local, held).reshape(-1)  # copies held elsewhere sort last
        is_zero = topi >= routed
        if dense:
            # Keys made distinct by the copy's index: a copy's row is how many keys are smaller.
            distinct = key * copies + jnp.arange(copies, dtype=jnp.int32)
            row = (distinct[None, :] < distinct[:, None]).sum(axis=1, dtype=jnp.int32).reshape(n, k)
            sizes = (key[:, None] == jnp.arange(held, dtype=jnp.int32)).sum(axis=0, dtype=jnp.int32)
        else:
            order = jnp.argsort(key, stable=True).astype(jnp.int32)
            sizes = jnp.bincount(key, length=held + 1)[:held].astype(jnp.int32)
        # Choices, identity choices and held choices of the valid tokens: per token, then one sum
        # (a sum over both axes at once compiles to three more operations a layer).
        tallies = jnp.stack([jnp.full((n,), k, jnp.int32), is_zero.sum(axis=1, dtype=jnp.int32),
                             here.sum(axis=1, dtype=jnp.int32)], axis=1)
        tallies = jnp.where(valid[:, None], tallies, 0).sum(axis=0)
        n_here = tallies[2]
        cap = held_rows_cap(copies, held, lp["router"].shape[-1])
        usual = n_here <= cap  # else the pass over every copy's rows: counted, a routing far from even
        counts = jnp.concatenate([tallies, (sizes > 0).sum(dtype=jnp.int32)[None], (~usual).astype(jnp.int32)[None]])

    if lp["router"].shape[-1] > routed:
        with jax.named_scope("moe.zero"):
            zero_w = jnp.where(is_zero, weights, 0.0).sum(axis=-1)  # f32[N]
            zero = x.astype(jnp.float32) * zero_w[:, None]
    else:  # a router without identity outputs: the held experts' terms are all there is
        zero = None

    if not fused:
        w_gate, w_up, w_down = _widen(lp, x.dtype)

    def one_pass(m: int) -> jnp.ndarray:
        """The layer's output from the first ``m`` rows: the held experts'
        terms summed in float32, the identity term added, in ``x``'s dtype (a
        ``cond``'s result crosses HBM: half the bytes)."""
        with jax.named_scope("moe.dispatch"):
            if dense:
                at = row[None, :, :] == jnp.arange(m, dtype=jnp.int32)[:, None, None]  # [m, N, k]: one 1 a row
                rows = jnp.dot(at.any(axis=2).astype(x.dtype), x, preferred_element_type=x.dtype)
            else:
                idx = jnp.pad(order, (0, max(0, m - copies)))[:m]
                rows = x[idx // k]
        if fused:
            from dynamo_tpu.ops.pallas_moe import expert_ffn_int8
            from dynamo_tpu.ops.pallas_paged import interpret_mode

            down = expert_ffn_int8(rows, lp["w_gate"], lp["w_up"], lp["w_down"], sizes,
                                   lp.get("expert_layer"), interpret=interpret_mode())
        else:
            with jax.named_scope("moe.experts_gate_up"):
                hidden = jax.nn.silu(jax.lax.ragged_dot(rows, w_gate, sizes)) * jax.lax.ragged_dot(rows, w_up, sizes)
            with jax.named_scope("moe.experts_down"):
                down = jax.lax.ragged_dot(hidden, w_down, sizes)
        with jax.named_scope("moe.combine"):
            live = jnp.arange(m) < n_here  # rows past the held copies were never computed
            down = jnp.where(live[:, None], down.astype(jnp.float32), 0.0)
            if dense:
                mix = jnp.where(at, weights[None, :, :], 0.0).sum(axis=2)  # f32[m, N]: a row's weight at its token
                terms = jnp.einsum("mn,md->nd", mix, down, precision=jax.lax.Precision.HIGHEST)
            else:
                terms = jnp.zeros((n, d), jnp.float32).at[idx // k].add(down * weights.reshape(-1)[idx][:, None])
            return (terms if zero is None else zero + terms).astype(x.dtype)

    if cap >= copies:
        return one_pass(cap), counts
    return jax.lax.cond(usual, lambda: one_pass(cap), lambda: one_pass(-(-copies // 16) * 16)), counts


def expert_capacity(num_tokens: int, num_experts: int, k: int, capacity_factor: float) -> int:
    """Per-expert buffer size: ceil(N*k/E * f), clamped to [k, N*k] and
    rounded up to a multiple of 8 (TPU sublane alignment)."""
    c = int(num_tokens * k * capacity_factor / num_experts + 0.999)
    c = max(k, min(c, num_tokens * k))
    return -(-c // 8) * 8


def moe_drop_stats(
    lp: dict,
    x: jnp.ndarray,  # [N, D] flattened tokens
    *,
    num_experts_per_token: int,
    capacity_factor: float = 1.25,
    capacity: int | None = None,
    routing: dict | None = None,
) -> tuple[int, int]:
    """(total choices, dropped choices) for this batch under the capacity
    dispatch's drop rule — the observability hook for drop rate (the
    dispatch itself is pure jit; this recomputes routing on demand, so call
    it on sampled batches, not the hot path)."""
    n = x.shape[0]
    e = lp["router"].shape[-1]
    k = num_experts_per_token
    c = capacity if capacity is not None else expert_capacity(n, e, k, capacity_factor)
    _w, topi = route_tokens(lp, x, k=k, **(routing or {}))
    flat_e = np.asarray(topi).reshape(-1)
    oh = np.eye(e, dtype=np.int64)[flat_e]
    pos = (np.cumsum(oh, axis=0) * oh).sum(-1) - 1
    dropped = int((pos >= c).sum())
    return n * k, dropped


def moe_mlp(
    lp: dict,
    x: jnp.ndarray,  # [N, D] flattened tokens
    *,
    num_experts_per_token: int,
    capacity_factor: float = 1.25,
    capacity: int | None = None,
    routing: dict | None = None,
) -> jnp.ndarray:
    """Routed MoE FFN over flattened tokens; returns [N, D].

    ``lp`` holds ``router [D, E]``, ``w_gate/w_up [E, D, F]``, ``w_down
    [E, F, D]`` (one layer's slice of the stacked params).
    """
    n, d = x.shape
    e = lp["router"].shape[-1]
    k = num_experts_per_token
    c = capacity if capacity is not None else expert_capacity(n, e, k, capacity_factor)

    with jax.named_scope("moe.router"):
        weights, topi = route_tokens(lp, x, k=k, **(routing or {}))

        # Buffer position of each (token, choice) within its expert: rank among
        # all earlier assignments to the same expert (token-major priority).
        flat_e = topi.reshape(-1)  # [N*k]
        oh = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)  # [N*k, E]
        pos = (jnp.cumsum(oh, axis=0) * oh).sum(-1) - 1  # [N*k]
        keep = pos < c
        slot = jnp.where(keep, pos, c)  # dropped choices land in a spill row

    if _drop_stats_enabled():
        jax.debug.callback(
            DROP_COUNTER.add, jnp.int32(n * k), (~keep).sum().astype(jnp.int32)
        )

    # Scatter tokens into expert buffers (+1 spill row, sliced off).
    with jax.named_scope("moe.router"):
        xk = jnp.repeat(x, k, axis=0)  # [N*k, D] — choice j of token t at t*k+j
        buf = jnp.zeros((e, c + 1, d), x.dtype).at[flat_e, slot].set(xk)
        expert_in = buf[:, :c]  # [E, C, D]

    # Batched expert FFN: one contraction over all experts; GSPMD shards the
    # leading axis on ep from the weight shardings.
    w_gate, w_up, w_down = _widen(lp, x.dtype)
    with jax.named_scope("moe.experts_gate_up"):
        gate = jax.nn.silu(jnp.einsum("ecd,edf->ecf", expert_in, w_gate))
        up = jnp.einsum("ecd,edf->ecf", expert_in, w_up)
    with jax.named_scope("moe.experts_down"):
        expert_out = jnp.einsum("ecf,efd->ecd", gate * up, w_down)  # [E, C, D]

    # Combine: gather each choice's row, weight, and sum over the k choices.
    with jax.named_scope("moe.combine"):
        rows = expert_out[flat_e, jnp.minimum(slot, c - 1)]  # [N*k, D]
        w = (weights.reshape(-1) * keep.astype(weights.dtype))[:, None]
        out = (rows.astype(jnp.float32) * w).reshape(n, k, d).sum(axis=1)
        return out.astype(x.dtype)
