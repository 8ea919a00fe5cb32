"""`RealDecodeEngine._readout`, a DP job's next tokens on the host:

  * each row's argmax, then each chunk's first token in chunk order, as
    Python ints equal to the per-row `int(jnp.argmax(...))` loop (the
    reference kept here), ties included: the argmax stays on the device
  * one `jax.device_get` per call, whatever the rows and chunks, counted
    as one `decode.host_transfers` beside the `decode.host_reads` of the
    ints it brings
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.serving import trace
from repro.serving.real_engine import RealDecodeEngine

VOCAB = 48


def _tied_logits(rows, seed, dtype):
    """(rows, VOCAB) logits of small integers, so each row holds its
    maximum more than once; row 0 is one value throughout."""
    x = np.random.default_rng(seed).integers(-3, 4, (rows, VOCAB))
    x[0] = 2
    return jnp.asarray(x, dtype)


def _per_row(logits, firsts=()):
    """The readout as one device-to-host read per `int`."""
    nxt = ([] if logits is None
           else [int(x) for x in jnp.argmax(logits, axis=-1)])
    return nxt, [int(jnp.argmax(lg[0])) for lg in firsts]


@pytest.fixture
def gets(monkeypatch):
    """Counts the `jax.device_get` calls the readout makes."""
    calls = []
    real = jax.device_get

    def counted(x):
        calls.append(x)
        return real(x)
    monkeypatch.setattr(jax, "device_get", counted)
    return calls


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("rows,chunks", [(1, 0), (16, 0), (33, 0),
                                         (16, 3), (0, 2)])
def test_readout_matches_the_per_row_loop(gets, recorder, rows, chunks,
                                          dtype):
    logits = _tied_logits(rows, 7 + rows, dtype) if rows else None
    # chunk firsts are last-position rows (1, VOCAB); distinct maxima
    # place each chunk's first token, so a reordering shows
    firsts = []
    for c in range(chunks):
        lg = _tied_logits(1, 100 + c, dtype).at[0, 5 * c + 1].set(9)
        firsts.append(lg)
    nxt, first = RealDecodeEngine._readout(logits, 3, tuple(firsts))
    want_nxt, want_first = _per_row(logits, firsts)
    assert (nxt, first) == (want_nxt, want_first)
    assert first == [5 * c + 1 for c in range(chunks)]
    if rows:
        ties = np.asarray(logits, np.float32)
        assert nxt == np.argmax(ties, axis=-1).tolist()
        assert nxt[0] == 0          # a row all one tie reads its first
        assert all((r == r.max()).sum() > 1 for r in ties)
    assert all(type(t) is int for t in nxt + first)
    assert len(gets) == 1
    (read,) = [s for s in trace.spans() if s.name == "decode.readout"]
    assert read.ids == {"dp": 3}
    assert read.counts == trace.counters() == {
        "decode.host_reads": rows + chunks, "decode.host_transfers": 1}
