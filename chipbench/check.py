"""The comparison that decides `correct`.

What the timed path produced is kept while it serves: for every token it
serves, the top `TOP_K` entries of the logits row the token was taken
from, on the device and without a host sync (`LogitCapture`).  After the
window, with the server freed, a sample of the window's finished requests
drawn from the seed (the longest among them) is compared with the plain
reference: one full-sequence forward pass per request over its prompt and
served tokens, jitted per length bucket (`Reference`).

Two numbers are compared, each with its limit from the configuration's
`check` block:

    logit_err_p90      the 90th percentile, over every compared token, of
                       the widest gap between the program's logits and
                       the reference's on the program's top-K entries,
                       in units of the reference row's standard deviation
                       (the program's logits divided by the fixed factor
                       its folded weights put on them); each compared
                       request's tokens together weigh as much as any
                       other request's;
    tokens_not_argmax  served tokens that are not the first entry of the
                       logits row they were taken from (exact: limit 0).

A high percentile and not the widest row: with random weights a router's
top-k lies within fp32 rounding of a tie at some positions in every few
thousand, and there the program and the reference may take different
discrete branches; the rows of that one request after it then differ by
a finite amount, a property of the weights, not of the code.  Weighing
requests alike bounds what one such request can move to its share of the
sample, under a tenth; a fault of the timed path that reaches a tenth of
the compared requests' rows (a stale cache, rows of another request, one
decode DP unit of two wrong) moves the percentile.  Besides, the count of
compared tokens must reach `min_tokens_compared`.
"""
from __future__ import annotations

import random
import threading
from typing import Dict, List, Optional, Tuple

TOP_K = 32
LOGIT_ERR_Q = 90        # the percentile of per-row gaps that is compared
SAMPLE_SALT = 0x5eed    # the sample's draw differs from the traffic's
ROW_PAD = 256           # compared rows per request padded to a multiple


class LogitCapture:
    """Hooks on the server's engines that keep, for every served token,
    the top-K values and ids of its logits row (device arrays).

    The decode step's logits are stashed by the worker thread as the step
    returns them and reduced to top-K on the runtime thread when the step
    is finished, so the timed step itself does no extra work.  The first
    token's row is the prefill chunk's logits at the prompt's last
    position.  `rows[rid][j]` is (values, ids, row) for served token j."""

    def __init__(self, srv, k: int = TOP_K):
        import jax
        self.topk = jax.jit(lambda x: jax.lax.top_k(x, k))
        self.rows: Dict[int, Dict[int, Tuple]] = {}
        self._tls = threading.local()
        self._pending: Dict[object, List] = {}
        self._lock = threading.Lock()
        spec = srv.spec
        self._spec = spec
        self._orig = {n: getattr(spec, n) for n in
                      ("jit_prefill_chunk", "jit_paged_decode",
                       "jit_decode") if hasattr(spec, n)}
        spec.jit_prefill_chunk = self._prefill_chunk
        if spec.paged:
            spec.jit_paged_decode = self._decode_step("jit_paged_decode")
        else:
            spec.jit_decode = self._decode_step("jit_decode")
        self._engines = list(srv.engines), list(srv.decode_engines)
        for eng in srv.engines:
            self._wrap_prefill(eng)
        for eng in srv.decode_engines:
            self._wrap_decode(eng)

    def reset(self) -> None:
        self.rows = {}

    def remove(self) -> None:
        """Restore the hooks and drop every reference to the server."""
        for n, f in self._orig.items():
            setattr(self._spec, n, f)
        for eng in self._engines[0]:
            eng.__dict__.pop("_run_chunk", None)
        for eng in self._engines[1]:
            eng.__dict__.pop("finish_step", None)
        self._spec, self._engines, self._orig = None, ((), ()), {}
        self._pending = {}

    # -- prefill ---------------------------------------------------------
    def _prefill_chunk(self, p, t, c):
        logits, new = self._orig["jit_prefill_chunk"](p, t, c)
        self._tls.last = logits
        return logits, new

    def _wrap_prefill(self, eng):
        run_chunk = eng._run_chunk

        def wrapped(req, tok):
            ctx = eng._ctx.get(req.rid)
            had = ctx is not None and ctx.first_token is not None
            self._tls.last = None
            run_chunk(req, tok)
            ctx = eng._ctx.get(req.rid)
            if (ctx is not None and ctx.first_token is not None
                    and not had and self._tls.last is not None):
                vals, ids = self.topk(self._tls.last)
                with self._lock:
                    self.rows.setdefault(req.rid, {})[0] = (vals, ids, 0)
        eng._run_chunk = wrapped

    # -- decode ----------------------------------------------------------
    def _decode_step(self, name):
        step = self._orig[name]

        def wrapped(p, t, c):
            logits, new = step(p, t, c)
            with self._lock:
                self._pending.setdefault(threading.current_thread(),
                                         []).append(logits)
            return logits, new
        return wrapped

    def _wrap_decode(self, eng):
        finish = eng.finish_step

        def wrapped(now, dp_states):
            with self._lock:
                got = self._pending.pop(eng._worker, [])
            res = eng._result or {}
            dps = list(res)
            if len(got) == 1 and len(dps) > 1:
                got = got * len(dps)        # one merged cross-DP job
            for dp, logits in zip(dps, got):
                vals, ids = self.topk(logits)
                for req, slot in eng._participants.get(dp, []):
                    j = len(eng.bus.gen(req.rid).tokens)
                    with self._lock:
                        self.rows.setdefault(req.rid, {})[j] = (vals, ids,
                                                                slot)
            return finish(now, dp_states)
        eng.finish_step = wrapped


def pick_sample(done: list, n: int, seed: int) -> list:
    """The finished request with the most served tokens, and `n - 1`
    more drawn from the seed."""
    if not done:
        return []
    longest = max(done, key=lambda r: (r.output_len, r.input_len, -r.rid))
    rest = sorted((r for r in done if r is not longest), key=lambda r: r.rid)
    k = min(n - 1, len(rest))
    return [longest] + random.Random(seed ^ SAMPLE_SALT).sample(rest, k)


def program_rows(cap: LogitCapture, sample: list, tokens: Dict[int, list]
                 ) -> Dict[int, Tuple]:
    """Per sampled rid: (values (n, K), ids (n, K)) of the program's
    rows for its n served tokens, read back to the host, or None where a
    row was not captured."""
    import jax
    import numpy as np
    out = {}
    for r in sample:
        got = cap.rows.get(r.rid, {})
        n = len(tokens[r.rid])
        if any(j not in got for j in range(n)):
            out[r.rid] = None
            continue
        parts = jax.device_get([(got[j][0], got[j][1]) for j in range(n)])
        vals = np.stack([v[got[j][2]] for j, (v, _i) in enumerate(parts)])
        ids = np.stack([i[got[j][2]] for j, (_v, i) in enumerate(parts)])
        out[r.rid] = (vals, ids)
    return out


def pad_to(xs: list, multiple: int, fill: int = 0) -> list:
    """`xs` padded at the end to a multiple of `multiple`."""
    return xs + [fill] * (-len(xs) % multiple)


class Reference:
    """The plain reference's logits at the positions that predicted the
    served tokens: one forward pass over prompt + served tokens per
    request, padded to a multiple of the model's query block, so each
    length bucket compiles once."""

    def __init__(self, model, conf: Dict, params):
        import jax
        self.model, self.conf, self.params = model, conf, params
        self._hidden = jax.jit(
            lambda p, t: model.reference_hidden(p, t, conf))
        self._rows = jax.jit(
            lambda p, h, at: model.reference_logits(p, h[at], conf))
        self._gaps = jax.jit(_row_gaps)
        self._topk = jax.jit(lambda x: jax.lax.top_k(x, TOP_K))

    def logits(self, req, toks: list):
        """(n_pad, V) device array: row j predicts served token j."""
        import jax.numpy as jnp
        seq = list(req.tokens[:req.input_len]) + list(toks)
        ids = jnp.asarray(pad_to(seq, self.model.Q_BLOCK), jnp.int32)
        at = [req.input_len - 1 + j for j in range(len(toks))]
        at = jnp.asarray(pad_to(at, ROW_PAD, fill=at[-1]), jnp.int32)
        return self._rows(self.params, self._hidden(self.params, ids), at)

    def control_logits(self, req, toks: list, precision: str):
        """`logits` with every matmul at `precision`."""
        import jax
        with jax.default_matmul_precision(precision):
            return self.logits(req, toks)

    def gaps(self, ref, vals, ids):
        """Per row: the widest |vals - ref[ids]| over the row's K
        entries, over the reference row's standard deviation."""
        import jax.numpy as jnp
        import numpy as np
        n = vals.shape[0]
        pad = ref.shape[0] - n
        v = jnp.asarray(np.pad(vals, ((0, pad), (0, 0))))
        i = jnp.asarray(np.pad(ids, ((0, pad), (0, 0))))
        return np.asarray(self._gaps(ref, v, i))[:n]

    def control_gaps(self, ref, low):
        """The control's gaps: the lower-precision reference `low` read
        as a program would be, on its own top-K entries."""
        import numpy as np
        vals, ids = self._topk(low)
        return np.asarray(self._gaps(ref, vals, ids))


def _row_gaps(ref, vals, ids):
    import jax.numpy as jnp
    took = jnp.take_along_axis(ref, ids, axis=-1)
    return jnp.max(jnp.abs(vals - took), -1) / jnp.std(ref, -1)


def compare(ref: Reference, sample: list, tokens: Dict[int, list],
            prog: Dict[int, Optional[Tuple]], logit_factor: float,
            control: Optional[str] = None) -> Dict:
    """Readings over the sample: per-row gaps of the program (or, with
    `control`, of the reference recomputed at that matmul precision),
    the served tokens that are not their row's first entry, and the
    tokens compared.  Each compared request's rows weigh 1 / its row
    count.  A request whose rows were not all captured counts every token
    as not compared and one token as off its row."""
    import numpy as np
    gaps: List[np.ndarray] = []
    off, n_tok, missing = 0, 0, 0
    for r in sample:
        toks = tokens[r.rid]
        if control is None and prog.get(r.rid) is None:
            missing += 1
            off += 1
            continue
        full = ref.logits(r, toks)
        if control is None:
            vals, ids = prog[r.rid]
            gaps.append(ref.gaps(full, vals / logit_factor, ids))
            off += int(np.sum(ids[:, 0] != np.asarray(toks)))
        else:
            low = ref.control_logits(r, toks, control)
            gaps.append(ref.control_gaps(full, low)[:len(toks)])
        n_tok += len(toks)
    return {"gaps": np.concatenate(gaps) if gaps else np.zeros(0),
            "weights": (np.concatenate([np.full(len(x), 1.0 / len(x))
                                        for x in gaps])
                        if gaps else np.zeros(0)),
            "off": off, "tokens": n_tok, "missing": missing}


def weighted_percentile(values, weights, q: float) -> float:
    """The least value whose rows, with every row below it, hold at
    least q % of the weight; infinite for an empty sample."""
    import numpy as np
    if not len(values):
        return float("inf")
    order = np.argsort(values, kind="stable")
    cum = np.cumsum(np.asarray(weights, float)[order])
    i = int(np.searchsorted(cum, q / 100.0 * cum[-1] * (1 - 1e-12)))
    return float(np.asarray(values)[order][min(i, len(cum) - 1)])


def decide(chk: Dict, reading: Dict) -> Tuple[bool, Dict]:
    """`correct`, and each number compared beside its limit."""
    err = weighted_percentile(reading["gaps"], reading["weights"],
                              LOGIT_ERR_Q)
    checks = {
        f"logit_err_p{LOGIT_ERR_Q}": {"value": err,
                                      "at_most": chk["logit_err"]},
        "tokens_not_argmax": {"value": reading["off"], "at_most": 0},
        "tokens_compared": {"value": reading["tokens"],
                            "at_least": chk["min_tokens_compared"]},
    }
    ok = (err <= chk["logit_err"] and reading["off"] == 0
          and reading["tokens"] >= chk["min_tokens_compared"])
    return bool(ok), checks
