"""What the model's layers count reaches ``stats()`` here, by one way.

A layer ``sow``s what only the device knows into ``intermediates``
(``ops/moe.MoEMLP``, ``Mamba2Mixer`` / ``KDAMixer``,
``LatentAttention``).  Every serving program returns it as ONE flat
float32 vector (``models/generate.sown_vector``) under a layout of
``(sown name, width)`` pairs that the engine discovers at construction
(``sown_layout`` over ``eval_shape``s of the decode model's calls); the
host reads it with the tick's tokens and :class:`ModelCounters` books
it under the ``stats()`` keys :data:`SOWN` names.  Beside them the
counters that need no device value: what a program's shape and the
slots' lengths say it had to read (``on_prefill``, ``on_decode``), by
the dispatch rules of ``ops/`` where they live.

A kernel that counts something new sows it and adds a row to
:data:`SOWN` (and its keys to :data:`KEYS`); ``engine.py`` is not
edited.
"""

from __future__ import annotations

import collections

import jax

from edl_tpu.ops import decode_attention

# What each sown name's entries are added to, a row a name: the stats()
# keys of its entries in a step program ("decode") and in a multi-token
# one ("prefill": prefill, chunk, reuse), and the key of how many layer
# calls sowed it ("decode_calls", "prefill_calls"); an entry under None,
# a mode a row leaves out: not booked.  KEYS says what each key counts
SOWN = {
    "moe_drops": {"decode": ("moe_prefill_drops",),         # capacity path
                  "prefill": ("moe_prefill_drops",)},
    # dropless path, a layer call: pairs computed here, distinct experts
    # touched, max-over-mean expert load and, where the layer holds a
    # share of the experts (moe_held), the pairs its router routed
    "moe_stats": {
        "decode": ("moe_assignments", "moe_decode_experts_touched", None,
                   "moe_assignments_routed"),
        "decode_calls": "moe_decode_layer_steps",
        "prefill": ("moe_assignments", "moe_prefill_experts_touched",
                    "moe_prefill_max_load_sum", "moe_assignments_routed"),
        "prefill_calls": "moe_prefill_groups"},
    # the kernels' own counts (ops/moe.decode_gmm, prefix_gmm), not sown
    # where ragged_dot runs
    "moe_fetched": {"decode": ("moe_decode_experts_fetched",)},
    "moe_prefix": {"prefill": ("moe_prefix_kernel_calls",)},
    # one-token calls: the kernel's fetch plan counted on the chip, every
    # slot (its whole slab) on the einsum path
    "ssm_slots_run": {"decode": ("ssm_state_steps_run",)},
    "latent_tokens_read": {"decode": ("latent_tokens_read",)},
    # a borrowing layer's one-row read of its lender's slab: whole attend
    # blocks on the chip, every slot's slab on the einsum path (booked
    # for the step programs; a prefill's cut row reads it too, unbooked)
    "borrowed_rows_read": {"decode": ("borrowed_kv_tokens_read",)},
}

# The scopes (``jax.named_scope``) a mixer kind's programs carry in a
# trace, a row a kind that names its own (``models/transformer.py``):
# what a reader of a device trace may look for beside the kernels' names
SCOPES = {
    "ssm": ("ssm/proj_in", "ssm/conv", "ssm/scan", "ssm/step",
            "ssm/gate_norm", "ssm/proj_out"),
    "kda": ("kda/proj_in", "kda/conv", "kda/step", "kda/gate_norm",
            "kda/proj_out"),
    "mamba1": ("mamba1/proj_in", "mamba1/conv", "mamba1/proj_x",
               "mamba1/scan", "mamba1/step", "mamba1/gate",
               "mamba1/proj_out"),
    "gmu": ("gmu",),
    "cross": ("attn/cross", "attn/diff_combine"),
    # a "global" layer's own: its multi-token call over the live prefix
    # in tiles (where ops/decode_attention.prefix_tiled holds) and its
    # output gate (TransformerConfig.attn_gate)
    "global": ("attn/prefix_chunk", "attn/out_gate"),
    # not a mixer kind: a block engine's pass program (block_length > 0)
    # and, on the chip, its kernels' names
    "block": ("block_pass", "attn/block_pass", "block_unmask",
              "block_append", "block_attend"),
}

# every model-counter key of stats(), in its order; 0.0: a float sum
KEYS = {
    # per token step of the plain decode program, summed: KV positions
    # live slots hold (prompt + emitted: what the step has to read) and
    # positions in the slabs (slots x max_len: what an unmasked read
    # touches)
    "decode_kv_tokens_live": 0, "decode_kv_tokens_slab": 0,
    # window layers (0s without one), per token step and live slot of
    # ONE window layer: ring positions the decode read fetched (whole
    # attend blocks on the chip, the ring off it) and positions the
    # window holds, min(length, window): read / need is 1 when a step
    # reads the window and no more, max_len / window when it reads a slab
    "decode_kv_tokens_window_read": 0, "decode_kv_tokens_window_need": 0,
    # head-row layers (a "global" layer's K / V slabs), per multi-token
    # call (prefill, chunk, reuse), lane and layer: rows up to the
    # call's last position, and the rows its attention read of the slab
    # (whole tiles up to there on the tiled path, the slab under its
    # mask on the dense one: read / live is 1 for a path that stops at
    # the slot's length, max_len / live for one that does not)
    "kv_prefill_rows_live": 0, "kv_prefill_rows_read": 0,
    # and the (query, visible row) pairs of those calls' real tokens, a
    # head-row layer (a real token at position p sees p + 1 rows)
    "kv_prefill_pairs": 0,
    # the bytes of one slot's state by cache class: the window layers'
    # rings, a recurrent state and (no key) nothing else are independent
    # of max_len; the global layers' slabs and the latent layers' rows
    # (one a token a layer, no head axis) are linear in it
    "kv_slot_bytes_window": 0, "kv_slot_bytes_global": 0,
    "kv_slot_bytes_state": 0, "kv_slot_bytes_latent": 0,
    # latent layers (0s without one), per token step, live slot and
    # layer: positions the decode read needed (the slot's length) and
    # fetched (SOWN)
    "latent_tokens_live": 0, "latent_tokens_read": 0.0,
    # and for the multi-token programs' expanded path: rows up to the
    # call's end, and the whole tiles read of them; those calls (lane x
    # latent layer), and the ones whose expanded path was the kernel and
    # not the XLA loop
    "latent_prefill_rows_live": 0, "latent_prefill_rows_read": 0,
    "latent_prefill_calls": 0, "latent_prefill_kernel_calls": 0,
    # the one-token calls that read ``latent_tokens_live``; the (query,
    # visible row) pairs of the multi-token calls' real tokens, a latent
    # layer, and those tokens (once)
    "latent_decode_calls": 0, "latent_prefill_pairs": 0,
    "latent_prefill_tokens": 0,
    # recurrent layers (0s without one): (slot, token step, layer)
    # states the step programs updated for LIVE slots, and all they read
    # and wrote (SOWN: equal when free slots cost nothing); positions
    # the prefill, chunk and reuse programs ran through the scan, and
    # those that were padding (masked: a scan cannot skip them for free)
    "ssm_state_steps": 0, "ssm_state_steps_run": 0.0,
    "ssm_prefill_positions": 0, "ssm_prefill_positions_pad": 0,
    # borrowing layers (0s without one), per token step, live slot and
    # borrowing layer: positions of the lender's slab the read needed
    # (the slot's length) and fetched (SOWN)
    "borrowed_kv_tokens_live": 0, "borrowed_kv_tokens_read": 0.0,
    # and what the sampling row of a multi-token program needed of them
    # (a lane's rows up to its last real token, a borrowing layer)
    "borrowed_kv_tokens_prefill": 0,
    # the last-position cut (0s for a stack with no tail): (real token,
    # layer) visits the multi-token programs ran, and those a full-depth
    # prefill of the same tokens would have run beside them
    "prefill_layer_visits": 0, "prefill_layer_visits_cut": 0,
    # MoE prefill capacity overflow (always 0 for dense configs;
    # nonzero = raise capacity_factor)
    "moe_prefill_drops": 0,
    # real tokens the host sent through an expert model's programs
    # (prompt tokens prefilled, live slots x token steps; 0 for dense
    # configs): on the dropless path moe_assignments == top_k x layers x
    # moe_tokens at every instant, and a path that drops reads less
    "moe_tokens": 0,
    # dropless expert path (0s otherwise): real (token, expert) pairs
    # computed; with a share of the experts held the pairs the routers
    # ROUTED, top_k x sparse layers x moe_tokens (without a share both
    # count the same pairs); layer calls of decode token steps, the
    # distinct experts they touched and the weight sets the kernel
    # fetched (equal when an untouched expert costs nothing); layer
    # calls of prefill programs, those of them that ran over the live
    # prefix alone, the experts they touched and their max-over-mean
    # expert load summed (ratio: the imbalance)
    "moe_assignments": 0, "moe_assignments_routed": 0,
    "moe_decode_layer_steps": 0, "moe_decode_experts_touched": 0,
    "moe_decode_experts_fetched": 0.0, "moe_prefill_groups": 0,
    "moe_prefix_kernel_calls": 0, "moe_prefill_experts_touched": 0,
    "moe_prefill_max_load_sum": 0.0,
}


class ModelCounters:
    """The model's counters, cumulative.  ``cfg`` is the decode
    configuration, ``classes`` its ``cache_layout.cache_classes``,
    ``one_lane`` one lane's cache skeleton.  ``on_prefill`` takes
    ``lock`` itself (and only where it books something); ``on_decode``,
    ``read`` and ``totals`` run under it."""

    def __init__(self, cfg, classes: dict, one_lane, slots: int,
                 layout: tuple, lock):
        self.layout, self._lock, self._cfg = layout, lock, cfg
        self.width = sum(w + 1 for _, w in layout)
        self._slab = slots * cfg.max_len
        self._t = dict(KEYS)
        by = collections.Counter(c.kind for c in classes.values())
        self._n_ring, self._n_state, self._n_latent = (
            by["window"], by["state"], by["latent"])
        self._n_borrowed = by["borrowed"]
        self._n_rows = by["global"]
        self._tail = cfg.num_layers - cfg.tail_start
        self._latent = next((c for c in classes.values()
                             if c.kind == "latent"), None)
        for kind in ("window", "global", "state", "latent"):
            self._t[f"kv_slot_bytes_{kind}"] = sum(
                leaf.size * leaf.dtype.itemsize
                for name, node in one_lane.items()
                if classes[name].kind == kind
                for leaf in jax.tree.leaves(node) if leaf.ndim > 1)
        # what one window layer's decode read fetches of a slot that
        # holds n ring positions: whole attend blocks on the kernels'
        # path, the ring on the einsum path
        R = cfg.ring_len
        self._ring_block = (
            decode_attention.attend_block(cfg.kv_heads, cfg.head_dim, R,
                                          cfg.dtype)
            if self._n_ring and decode_attention.applies(1, cfg.mesh, R)
            else R)
        # the step programs of a stack whose layers sow moe_stats count
        # their tokens; moe_stats without a fourth entry: every pair is
        # computed where it is routed
        widths = dict(layout)
        self._steps_route = "moe_stats" in widths
        self._routed_sown = widths.get("moe_stats", 0) > 3

    def on_prefill(self, lanes: int, width: int, real: int, offset: int = 0,
                   lens=None, final: bool = True) -> None:
        """One prefill, chunk or reuse program ran ``lanes x width``
        positions from ``offset`` on, ``real`` of them tokens (``lens``
        a lane where there are several), through every recurrent
        layer's scan, every head-row layer's attention over its slab
        and every latent layer's expanded path (rows up to
        the call's end, read in whole tiles of the path that ran, the
        kernel or the loop: the rule and the plan the program was built
        under, nothing read back from the device).  ``final``: the
        program sampled (a stack with a tail ran it at a row a lane; a
        chunk that samples nothing left it out)."""
        if not (self._n_state or self._n_latent or self._tail
                or self._n_rows):
            return
        t = self._t
        with self._lock:
            if self._n_rows:
                cfg, end = self._cfg, offset + width
                read = cfg.max_len
                if decode_attention.prefix_tiled(width, cfg.num_heads,
                                                 cfg.max_len):
                    tk = decode_attention.prefix_block(
                        lanes, width, cfg.num_heads, cfg.max_len)
                    read = -(-end // tk) * tk
                t["kv_prefill_rows_live"] += lanes * self._n_rows * end
                t["kv_prefill_rows_read"] += lanes * self._n_rows * read
                t["kv_prefill_pairs"] += self._n_rows * sum(
                    n * offset + n * (n + 1) // 2
                    for n in ([real] if lens is None else lens))
            if self._tail:
                ran = (real * self._cfg.tail_start
                       + (lanes * self._tail if final else 0))
                t["prefill_layer_visits"] += ran
                t["prefill_layer_visits_cut"] += (
                    real * self._cfg.num_layers - ran)
                if final:
                    t["borrowed_kv_tokens_prefill"] += self._n_borrowed * sum(
                        offset + n for n in ([real] if lens is None else lens))
            if self._n_state:
                t["ssm_prefill_positions"] += lanes * width
                t["ssm_prefill_positions_pad"] += lanes * width - real
            if self._n_latent:
                calls, end = lanes * self._n_latent, offset + width
                tk = self._latent.tile(lanes, width, self._cfg.num_heads)
                t["latent_prefill_rows_live"] += calls * end
                t["latent_prefill_rows_read"] += calls * -(-end // tk) * tk
                t["latent_prefill_calls"] += calls
                t["latent_prefill_kernel_calls"] += (
                    calls * self._latent.tiled(width))
                t["latent_prefill_tokens"] += real
                # a real token at offset + i sees offset + i + 1 rows
                t["latent_prefill_pairs"] += self._n_latent * sum(
                    n * offset + n * (n + 1) // 2
                    for n in ([real] if lens is None else lens))

    def on_decode(self, held: list, live: int, T: int) -> None:
        """A step program ran ``T`` token steps with ``live`` slots
        live; ``held``: the positions each read of a slot that still
        owns its request reached, a slot and token step."""
        t, kv_live = self._t, sum(held)
        t["decode_kv_tokens_live"] += kv_live
        t["decode_kv_tokens_slab"] += self._slab * T
        if self._n_ring:
            R, tk, W = (self._cfg.ring_len, self._ring_block,
                        self._cfg.attn_window)
            t["decode_kv_tokens_window_read"] += sum(
                -(-min(n, R) // tk) * tk for n in held)
            t["decode_kv_tokens_window_need"] += sum(min(n, W) for n in held)
        t["ssm_state_steps"] += live * T * self._n_state
        t["latent_tokens_live"] += kv_live * self._n_latent
        t["latent_decode_calls"] += len(held) * self._n_latent
        t["borrowed_kv_tokens_live"] += kv_live * self._n_borrowed

    def read(self, vector, tokens: int, decode: bool) -> None:
        """Book one program's ``sown_vector`` (on the host),
        and beside it the ``tokens`` the host knows it routed."""
        t = self._t
        if self._steps_route if decode else self._cfg.moe_experts:
            t["moe_tokens"] += tokens
        mode, values, at = ("decode" if decode else "prefill",
                            vector.tolist(), 0)
        for name, width in self.layout:
            row = SOWN.get(name, {})
            for key, value in (*zip(row.get(mode, ()), values[at:at + width]),
                               (row.get(mode + "_calls"), values[at + width])):
                if key:
                    t[key] += value if type(KEYS[key]) is float else int(value)
            at += width + 1

    def totals(self) -> dict:
        out = {k: round(v, 3) if type(v) is float else v
               for k, v in self._t.items()}
        if not self._routed_sown:
            out["moe_assignments_routed"] = out["moe_assignments"]
        return out
