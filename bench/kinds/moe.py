"""Kind `moe`: batches of tokens through one chip's share of an MoE FFN layer.

The configuration is a published MoE model's FFN layer (DeepSeek-V3's) cut
to one chip of an expert-parallel deployment: the full router, the shared
expert and `experts_held` routed experts (ids 0 to experts_held - 1), on
the configuration's crossbar tiles. Weights and router bias are drawn
from the seed on the device (`init_std`, `router_bias_std`), one matrix
at a time, and mapped once in set-up (`repro.core.evaluate.map_moe`).

Each call is one `repro.core.evaluate.evaluate_moe` call on a batch of
`tokens` tokens drawn as N(0, 1) from the run's seed and the call's
index (a closed loop, one client). A call's points are the (token,
expert) pairs it computed, group `shared` or `routed`: one eval is one
token through one expert's gate, up and down crossbars.

Set-up warms every program a window can use: besides the shared expert
(all tokens), a held expert can see 1 to `tokens` tokens, which the
program rounds up to a power of two. The warm-up batches are made of
tokens that the float64 reference router sends to exactly one held
expert, so many to one expert that each count of slots is reached.

The check takes `check_calls` seeded calls. In each, the float64 router
(`benchlib.moe_reference`) routes the call's tokens, and two pairs are
checked: the shared and one held routed expert of a seeded token that
has one, else the shared expert of two seeded tokens. For each pair the
reference solves seeded column blocks (`check_blocks`: of gate, up and
down), every tile by a sparse LU, each driven by the program's own input
to that matrix: the token for gate and up, the program's silu(gate) * up
for down. Numbers:

  z_rel_err          the largest relative L2 gap between a checked
                     block's sensed outputs and the reference's;
  dev_power_rel_err  the largest relative gap of a checked block's device
                     power (the sum of its tiles');
  out_rel_err        the relative L2 gap of each checked token's partial
                     output (shared + weighted held experts) on the checked
                     down blocks' columns, with the reference's routing
                     weights and blocks (the token's unchecked held pairs
                     enter with the program's outputs);
  route_mismatch     the tokens of the call whose selection (all
                     num_experts_per_tok ids), routing weights (relative
                     WEIGHT_RTOL), or computed pairs differ from the float64
                     router's. A token whose float64 margin is under
                     ROUTE_MARGIN is left out: float32 rounding decides it.

A pair the reference routes here and the program did not compute reads
infinity, as does a number that is not finite.

Controls: `control_bf16`, the program's crossbars in bfloat16
(`IMACConfig.dtype`), one precision below the float32 the configuration
states (the router stays float32); `control_no_group_limit`, the program's
router with every group kept: a plain top-k.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import data, moe_reference
from benchlib.traffic import Call, Traffic

NUMBERS = ("z_rel_err", "dev_power_rel_err", "out_rel_err", "route_mismatch")
CONTROLS = ("control_bf16", "control_no_group_limit")

#: A float64 router margin under which float32 scores may decide otherwise:
#: scores are sigmoids near 0.5, a float32 score is within about 1e-6.
ROUTE_MARGIN = 1e-5
#: Relative gap at which a program's routing weight differs.
WEIGHT_RTOL = 1e-5
SHARED = moe_reference.SHARED


def slots(n: int) -> int:
    """The program's slot count for an expert given n tokens: a power of two."""
    return 1 << (n - 1).bit_length()


@dataclasses.dataclass(frozen=True)
class PairPoint:
    """One (token, expert) pair of a call: an evaluation unit."""

    name: str     # "t<token>/shared" or "t<token>/e<expert>"
    group: str    # "shared" or "routed"


@dataclasses.dataclass
class MoECall(Call):
    """One `evaluate_moe` call; `results` are its pairs."""

    x: np.ndarray = None    # (tokens, hidden_size) the call's tokens
    result: object = None   # the program's MoEResult

    @property
    def tokens(self) -> int:
        return len(self.x)


@functools.partial(jax.jit, static_argnames=("shape",))
def _normal(key, shape, std):
    return std * jax.random.normal(key, shape, jnp.float32)


class Weights:
    """The run's weights, drawn on the device from the seed, one matrix at a
    time, so that the check can draw a checked expert's again."""

    def __init__(self, cfg: dict, seed: int):
        self.cfg, self.seed = cfg, seed

    def matrix(self, expert: int, which: int):
        """Gate (0), up (1) or down (2) of `expert` (SHARED for the shared one),
        in x @ W orientation."""
        d, f = self.cfg["hidden_size"], self.cfg["moe_intermediate_size"]
        shape = (f, d) if which == 2 else (d, f)
        key = jax.random.PRNGKey(data.derive(self.seed, 8, expert + 1, which))
        return _normal(key, shape, self.cfg["init_std"])

    def expert(self, expert: int):
        return tuple(self.matrix(expert, which) for which in range(3))

    def router(self):
        n, d = self.cfg["n_routed_experts"], self.cfg["hidden_size"]
        w = _normal(jax.random.PRNGKey(data.derive(self.seed, 9, 0)), (n, d),
                    self.cfg["init_std"])
        b = _normal(jax.random.PRNGKey(data.derive(self.seed, 9, 1)), (n,),
                    self.cfg["router_bias_std"])
        return w, b


def program_layer(cfg: dict, weights: Weights, dtype=None, group_limit=True):
    """The program's `MoELayer` of this chip's experts."""
    from repro.core.evaluate import map_moe
    from repro.core.imac import IMACConfig
    from repro.core.moe import SHARED as PROGRAM_SHARED, MoESpec

    spec = MoESpec(
        d_model=cfg["hidden_size"], d_expert=cfg["moe_intermediate_size"],
        n_experts=cfg["n_routed_experts"], n_shared=cfg["n_shared_experts"],
        top_k=cfg["num_experts_per_tok"], n_group=cfg["n_group"],
        topk_group=cfg["topk_group"] if group_limit else cfg["n_group"],
        scaling=cfg["routed_scaling_factor"], norm_topk_prob=cfg["norm_topk_prob"],
        scoring_func=cfg["scoring_func"],
    )
    imac = cfg["imac"]
    kw = dict(tech=imac["technology"]["name"], array_rows=imac["array_rows"],
              array_cols=imac["array_cols"], vdd=imac["vdd"], vss=imac["vss"])
    if dtype is not None:
        kw["dtype"] = dtype
    experts = {PROGRAM_SHARED: weights.expert(SHARED)}
    experts.update({e: weights.expert(e) for e in range(cfg["experts_held"])})
    w_router, bias = weights.router()
    return map_moe(spec, IMACConfig(**kw), w_router, bias, experts)


class Batches:
    """Drives `evaluate_moe` calls; the window is `Traffic`'s."""

    label = "batch"
    n_samples = 1          # one eval is one (token, expert) pair
    parasitics = True
    window = Traffic.window

    def __init__(self, cfg: dict, mix: dict, seed: int, weights: Weights, layer):
        self.cfg, self.mix, self.seed = cfg, mix, seed
        self.weights, self.layer = weights, layer
        self.n_tokens = int(mix["tokens"])
        w_router, bias = weights.router()
        self.w_router, self.bias = np.asarray(w_router), np.asarray(bias)
        self._next = 0

    def tokens(self, index: int):
        key = jax.random.PRNGKey(data.derive(self.seed, 7, index))
        return _normal(key, (self.n_tokens, self.cfg["hidden_size"]), 1.0)

    def next_points(self):
        return None     # a call's pairs are known once the router has run

    def call(self, points, label: str, x=None) -> MoECall:
        from repro.core.evaluate import evaluate_moe

        index = -1
        if x is None:
            index, self._next = self._next, self._next + 1
            x = self.tokens(index)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(label):
            res = evaluate_moe(self.layer, x)
        t1 = time.perf_counter()
        pts = [PairPoint(f"t{p.token}/" + ("shared" if p.expert == SHARED else f"e{p.expert}"),
                         "shared" if p.expert == SHARED else "routed")
               for p in res.pairs]
        return MoECall(pts, index, t0, t1, list(res.pairs), x=np.asarray(x), result=res)

    def inputs(self, call: MoECall):
        return call.x

    # -- set-up --------------------------------------------------------------
    def warmup_batches(self, attempt: int, wanted) -> "list[np.ndarray]":
        """Batches in which one held expert each is sent min(s, tokens) tokens,
        for every slot count s of `wanted`."""
        cfg = self.cfg
        held = cfg["experts_held"]
        rng = np.random.default_rng(data.derive(self.seed, 10, attempt))
        cand = rng.standard_normal((64 * self.n_tokens, cfg["hidden_size"]))
        idx, _, margin = moe_reference.route(cand, self.w_router, self.bias, cfg)
        single = {e: [] for e in range(held)}
        none = []
        for t in range(len(cand)):
            if margin[t] < 100 * ROUTE_MARGIN:
                continue
            mine = [int(e) for e in idx[t] if e < held]
            if not mine:
                none.append(t)
            elif len(mine) == 1:
                single[mine[0]].append(t)
        batches = []
        for s in sorted(wanted, reverse=True):
            n = min(s, self.n_tokens)
            e = max(single, key=lambda k: len(single[k]), default=None)
            if e is None or len(single[e]) < n:
                continue
            rows = single.pop(e)[:n]
            room = [b for b in batches if len(b) + n <= self.n_tokens]
            if room:
                room[0].extend(rows)
            else:
                batches.append(rows)
        out = []
        for b in batches:
            fill = self.n_tokens - len(b)
            if len(none) < fill:
                break
            b.extend(none[:fill])
            del none[:fill]
            out.append(cand[b].astype(np.float32))
        return out

    def warm_up(self) -> None:
        self.tokens(0).block_until_ready()
        wanted = {slots(n) for n in range(1, self.n_tokens + 1)}
        for attempt in range(4):
            for x in self.warmup_batches(attempt, wanted):
                res = self.call(None, "warmup", x=x).result
                counts = {}
                for p in res.pairs:
                    if p.expert != SHARED:
                        counts[p.expert] = counts.get(p.expert, 0) + 1
                wanted -= {slots(n) for n in counts.values()}
            if not wanted:
                return
        print(f"bench: warm-up reached no held expert with slot counts {sorted(wanted)}",
              file=sys.stderr, flush=True)


def build(cfg: dict, mix: dict, seed: int, control=None):
    """(batches, notes): this chip's experts mapped from the seed's weights."""
    if control not in (None,) + CONTROLS:
        raise ValueError(f"unknown control {control!r}; known: {CONTROLS}")
    weights = Weights(cfg, seed)
    layer = program_layer(
        cfg, weights, dtype=jnp.bfloat16 if control == "control_bf16" else None,
        group_limit=control != "control_no_group_limit")
    batches = Batches(cfg, mix, seed, weights, layer)
    return batches, {"experts_held": cfg["experts_held"], "tokens": batches.n_tokens}


def failed(calls) -> int:
    """Pairs whose power or token output is not finite."""
    return sum(1 for c in calls for p in c.results
               if not (math.isfinite(p.power) and np.all(np.isfinite(c.result.out[p.token]))))


def _rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    value = float(np.linalg.norm(a - b) / np.linalg.norm(b))
    return value if math.isfinite(value) else math.inf


def check_call(traffic: Batches, call: MoECall, rng) -> "tuple[dict, list]":
    """The numbers of one call, and per checked pair its own."""
    cfg = traffic.cfg
    imac = cfg["imac"]
    rows, cols = imac["array_rows"], imac["array_cols"]
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    if f % cols:
        raise ValueError("gate and up share tiles: expert width not a multiple of cols")
    held = set(range(cfg["experts_held"]))
    res, x = call.result, call.x
    idx, weight, margin = moe_reference.route(x, traffic.w_router, traffic.bias, cfg)
    pairs = {(p.token, p.expert): p for p in res.pairs}

    nums = {k: 0.0 for k in NUMBERS}
    ref_pairs = {}
    weight_gap = 0.0
    for t in range(len(x)):
        want = {(t, SHARED): 1.0}
        want.update({(t, int(e)): w for e, w in zip(idx[t], weight[t]) if e in held})
        ref_pairs.update(want)
        if margin[t] < ROUTE_MARGIN:
            continue
        prog = dict(zip(res.topk_idx[t].tolist(), res.topk_weight[t].tolist()))
        ref = dict(zip(idx[t].tolist(), weight[t].tolist()))
        same = prog.keys() == ref.keys() and all(
            abs(prog[e] - w) <= WEIGHT_RTOL * abs(w) for e, w in ref.items())
        if prog.keys() == ref.keys():
            weight_gap = max(weight_gap, max(abs(prog[e] - w) / w for e, w in ref.items()))
        got = {k for k in pairs if k[0] == t}
        if got != set(want) or not same or any(
                abs(pairs[k].weight - w) > WEIGHT_RTOL * abs(w) for k, w in want.items()):
            nums["route_mismatch"] += 1

    print(f"bench: moe route tokens={len(x)} under_margin={int(np.sum(margin < ROUTE_MARGIN))} "
          f"min_margin={float(np.min(margin))} weight_rel_gap={weight_gap}",
          file=sys.stderr, flush=True)
    clear = [t for t in range(len(x)) if margin[t] >= ROUTE_MARGIN] or list(range(len(x)))
    routed = [t for t in clear if any(k[0] == t and k[1] != SHARED for k in ref_pairs)]
    if routed:
        t = routed[rng.integers(len(routed))]
        experts = sorted(k[1] for k in ref_pairs if k[0] == t and k[1] != SHARED)
        checks = [(t, SHARED), (t, experts[rng.integers(len(experts))])]
    else:
        t1, t2 = rng.choice(clear, size=2, replace=len(clear) < 2)
        checks = [(int(t1), SHARED), (int(t2), SHARED)]
    blocks = traffic.mix["check_blocks"]
    n_up, n_down = f // cols, math.ceil(d / cols)
    chosen = {m: sorted(rng.choice(n, size=min(blocks[m], n), replace=False).tolist())
              for m, n in (("gate", n_up), ("up", n_up), ("down", n_down))}
    if any(k not in pairs for k in checks):
        inf = {k: math.inf for k in NUMBERS}
        return inf, [(f"t{t}/{e} blocks={chosen}", inf) for t, e in checks]

    # Tiles of the program's fused [gate | up] matrix and of down:
    # tile t = h * vp + v, the G+ tiles, then the G- tiles.
    hp_up, vp_up = math.ceil(d / rows), 2 * n_up
    hp_down, vp_down = math.ceil(f / rows), n_down

    def tiles(hp, vp, v):
        first = [h * vp + v for h in range(hp)]
        return first + [hp * vp + i for i in first]

    jobs, meta = [], []
    for t, e in checks:
        p = pairs[(t, e)]
        ws = traffic.weights.expert(e)
        scales = [float(jnp.max(jnp.abs(w))) for w in ws]
        for m, which, a in (("gate", 0, x[t]), ("up", 1, x[t]), ("down", 2, p.detail.h)):
            for j in chosen[m]:
                cut = np.asarray(ws[which][:, j * cols:(j + 1) * cols])
                jobs.append((moe_reference.Block(cfg, cut, scales[which], rows), a))
                meta.append((t, e, m, j))
        del ws
    solved = moe_reference.solve_blocks(cfg, jobs)

    per_pair = {k: {"z_rel_err": 0.0, "dev_power_rel_err": 0.0} for k in checks}
    y_ref = {}
    for (t, e, m, j), (z_ref, p_ref) in zip(meta, solved):
        det = pairs[(t, e)].detail
        if m == "down":
            z = det.z_down[j * cols:(j + 1) * cols]
            p = np.sum(det.tile_power_down[tiles(hp_down, vp_down, j)], dtype=np.float64)
            y_ref.setdefault((t, e), []).append(z_ref)
        else:
            v = j if m == "gate" else n_up + j
            z = det.z_up[v * cols:(v + 1) * cols]
            p = np.sum(det.tile_power_up[tiles(hp_up, vp_up, v)], dtype=np.float64)
        err = per_pair[(t, e)]
        err["z_rel_err"] = max(err["z_rel_err"], _rel(z, z_ref))
        err["dev_power_rel_err"] = max(err["dev_power_rel_err"],
                                       _rel(p, p_ref) if p_ref > 0 else math.inf)

    columns = np.concatenate([np.arange(j * cols, min((j + 1) * cols, d))
                              for j in chosen["down"]])
    for t in sorted({t for t, _ in checks}):
        expect = np.zeros(columns.size)
        for (tt, e), w in ref_pairs.items():
            if tt != t:
                continue
            if (t, e) in y_ref:
                y = np.concatenate(y_ref[(t, e)])
            elif (t, e) in pairs:
                y = pairs[(t, e)].detail.z_down[columns]
            else:
                expect = np.full(columns.size, np.nan)
                break
            expect = expect + w * y
        nums["out_rel_err"] = max(nums["out_rel_err"], _rel(res.out[t, columns], expect))
    for err in per_pair.values():
        for k, v in err.items():
            nums[k] = max(nums[k], v)
    return nums, [(f"t{t}/{e} blocks={chosen}", err) for (t, e), err in per_pair.items()]


def compare(traffic: Batches, calls, seed: int) -> dict:
    """Worst value of each number over the checked calls."""
    rng = np.random.default_rng(data.derive(seed, 4))
    n = min(int(traffic.mix.get("check_calls", 1)), len(calls))
    chosen = sorted(rng.choice(len(calls), size=n, replace=False))
    worst = {k: 0.0 for k in NUMBERS}
    per_point = []
    for ci in chosen:
        nums, points = check_call(traffic, calls[int(ci)], rng)
        for k, v in nums.items():
            worst[k] = max(worst[k], v)
        per_point += points
    worst["checked_points"] = len(per_point)
    worst["per_point"] = per_point
    return worst
