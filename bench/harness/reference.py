"""The plain reference that decides ``correct``.

It imports nothing of the program. It reads the program's answers (the
logged steps, the sampled steps' extent streams, transactions and
finish times) and holds them against what the configuration file
states: the layer equations and KV geometry (recorder bytes), the
stripe map (census and transactions), the controller and its timing
tables (:mod:`.channel`, cycle steps) and the channels' peak pin
bandwidth (analytic steps). The numbers compared, each an exact count
with its limit in ``bench/checks.json``:

``bytes_bad``
    Logged steps or sampled (step, request) byte totals that disagree
    with the request lengths: admission out of arrival order, more
    decoders than slots, a request finishing after other than its
    output length, weight bytes other than the layer equations give, KV
    reads other than whole pages of the request's context, KV appends
    other than one token.
``census_bad``
    Sampled steps whose per-channel bytes (whole stripe units) differ
    from the reference's stripe census.
``clock_bad``
    Steps whose start is not the previous step's end on a replica that
    still had work, or, on an idle (or new) replica, not the later of
    that end and the arrival of the newest request it admits.
``txn_bad``
    Sampled cycle steps' channels whose transactions (arrival, bank,
    row, column, direction, in order) differ from the stripe map's.
``sched_bad``
    Transactions of sampled cycle steps whose finish time differs from
    the plain channel model's, plus steps whose duration is not the
    last finish. The model runs on sampled steps up to
    :data:`SCHED_TXNS` transactions, the largest step first.
``floor_bad``
    Sampled analytic steps priced faster than their busiest channel's
    bytes (whole rows on a row-granular controller) can cross its pins.

``answers="control"`` puts the reference, computed in float32, in the
program's place: the model's finish times and step durations (the
floor, for analytic steps) and the clock they advance.
"""
from __future__ import annotations

import numpy as np

from . import channel

ROW = 4096
#: Transactions of sampled cycle steps the channel model schedules in a
#: run: about 6 s of host time on HBM4 decode's 18,886-transaction steps.
SCHED_TXNS = 100_000


# ---------------------------------------------------------------------------
# Layer equations: the scaled weight slice of one decode step
# ---------------------------------------------------------------------------

def weight_slice_bytes(model: dict, n_ops: int, scale: float) -> int:
    """Read bytes of the first ``n_ops`` decode ops' weights on one
    device (batch 1), each extent scaled and floored at one row."""
    m = model
    d, hd = m["d_model"], m["head_dim"]
    bpp, ndev = m["bytes_per_param"], m["n_devices"]
    if m["mla_kv_lora"]:
        attn = (d * m["mla_q_lora"]
                + m["mla_q_lora"] * m["n_heads"] * (hd + m["mla_rope_dim"])
                + d * (m["mla_kv_lora"] + m["mla_rope_dim"])
                + m["mla_kv_lora"] * m["n_heads"] * 2 * hd
                + m["n_heads"] * hd * d) * bpp
    else:
        tp = m["attn_tp"]
        attn = (d * m["n_heads"] * hd // tp
                + 2 * d * m["n_kv_heads"] * hd // tp
                + m["n_heads"] * hd * d // tp) * bpp
    moe = m["n_experts"] > 0
    extents = []
    for layer in range(m["n_layers"]):
        extents.append([attn])
        if moe and layer >= m["n_dense_layers"]:
            expert = 3 * d * m["d_ff"] * bpp
            e_dev = m["n_experts"] // m["moe_ep"]
            active = e_dev * (1.0 - (1.0 - m["top_k"] / m["n_experts"]))
            ffn = [expert] * max(1, round(active))
            if m["n_shared_experts"]:
                ffn.append(m["n_shared_experts"] * expert)
            extents.append(ffn)
        elif moe:
            extents.append([3 * d * m["dense_d_ff"] // ndev * bpp])
        else:
            extents.append([3 * d * m["d_ff"] // ndev * bpp])
        if len(extents) >= n_ops:
            break
    return sum(max(ROW, int(n * scale))
               for op in extents[:n_ops] for n in op if n > 0)


def kv_page(kv: dict) -> tuple[int, int, int]:
    """``(pools, bytes per token per pool, tokens per page)``."""
    per_tok = ((kv["kv_lora_rank"] + kv["qk_rope_head_dim"]) // kv["pools"]
               * kv["itemsize"])
    return (kv["pools"], per_tok,
            kv["rows_per_page"] * kv["row_bytes"] // per_tok)


# ---------------------------------------------------------------------------
# Stripe census
# ---------------------------------------------------------------------------

def stream_columns(stream):
    """``(addr, nbytes, is_write, arrival, stream_id)`` arrays."""
    recs = list(stream)
    return (np.array([r.addr for r in recs], np.int64),
            np.array([r.nbytes for r in recs], np.int64),
            np.array([r.kind == "write" for r in recs], bool),
            np.array([r.arrival_ns for r in recs], np.float64),
            np.array([r.stream_id for r in recs], np.int64))


def units_per_channel(addr, nbytes, stripe: int, nch: int):
    """(records, channels) whole stripe units each record puts on each
    channel: the units u in [first, last] with u mod nch == c."""
    first = addr // stripe
    last = (addr + nbytes - 1) // stripe
    c = np.arange(nch)[None, :]
    return ((last[:, None] - c) // nch) - ((first[:, None] - 1 - c) // nch)


def floor_ns(cols, mem: dict, dt=np.float64) -> float:
    """The least time the busiest channel needs to move a step's bytes
    at its peak pin bandwidth: whole rows on a row-granular controller
    (every unit it touches is a row it moves), the exact bytes
    otherwise."""
    addr, nbytes, _, _, _ = cols
    g, nch = mem["stripe_bytes"], mem["n_channels"]
    if g >= mem["row_bytes"]:
        per_ch = units_per_channel(addr, nbytes, g, nch).sum(axis=0) * g
    else:
        first, last = addr // g, (addr + nbytes - 1) // g
        units = units_per_channel(addr, nbytes, g, nch)
        per_ch = (units * g).sum(axis=0)
        np.subtract.at(per_ch, first % nch, addr - first * g)
        np.subtract.at(per_ch, last % nch, (last + 1) * g - (addr + nbytes))
    bw = dt(channel.peak_channel_gbps(mem))
    return float(dt(per_ch.max(initial=0)) / bw)


# ---------------------------------------------------------------------------
# The comparison
# ---------------------------------------------------------------------------

def _same_txns(got, want) -> bool:
    arr, bank, row, col, wr = want
    return (len(got) == arr.size
            and np.array_equal([t.arrival_ns for t in got], arr)
            and np.array_equal([t.bank for t in got], bank)
            and np.array_equal([t.row for t in got], row)
            and np.array_equal([t.col for t in got], col)
            and np.array_equal([t.is_write for t in got], wr))


def _modelled(checked: dict, budget: int) -> set:
    """Log indices of the cycle steps the channel model runs: the
    largest first, then in log order, up to ``budget``
    transactions (at least one step)."""
    sizes = {k: sum(len(v) for v in res.channel_txns.values())
             for k, (_, _, res) in checked.items() if res.mode == "cycle"}
    order = sorted(sizes, key=lambda k: -sizes[k])[:1] + sorted(sizes)
    out, spent = set(), 0
    for k in order:
        if k in out:
            continue
        if out and spent + sizes[k] > budget:
            break
        out.add(k)
        spent += sizes[k]
    return out


def check(config: dict, traffic: dict, reqs, log: list, steps: list,
          answers: str = "program") -> dict[str, float]:
    """Readings of every number compared (module docstring)."""
    if answers not in ("program", "control"):
        raise ValueError(answers)
    if traffic.get("prefill_chunk_tokens") or traffic.get("warm"):
        raise NotImplementedError(
            "the reference covers legacy prefill under reset steps only")
    mem = dict(config["memory"], n_channels=config["n_channels"])
    pools, per_tok, page_tokens = kv_page(config["kv"])
    page_bytes = page_tokens * per_tok
    weights = weight_slice_bytes(config["model"], int(traffic["n_ops"]),
                                 float(traffic["step_scale"]))
    n_slots = int(traffic["n_slots"])
    checked = {i: (replica, st, res) for i, replica, st, res in steps}

    # -- the reference's answers for the sampled steps ---------------------
    cols = {k: stream_columns(st.stream) for k, (_, st, _) in checked.items()}
    modelled = _modelled(checked, SCHED_TXNS)
    txn_bad = sched_bad = floor_bad = census_bad = 0
    ref_fin: dict = {}
    ref_dur: dict = {}
    for k, (_, st, res) in checked.items():
        addr, nbytes, is_w, arrival, _ = cols[k]
        units = units_per_channel(addr, nbytes, mem["stripe_bytes"],
                                  mem["n_channels"]).sum(axis=0)
        if not np.array_equal(units * mem["stripe_bytes"],
                              np.asarray(res.channel_bytes)):
            census_bad += 1
        if res.mode != "cycle":
            ref_dur[k] = floor_ns(cols[k], mem)
            continue
        start = log[k][4]
        txns = channel.decompose(addr, nbytes, is_w, arrival - start, mem)
        txn_bad += len(set(txns) ^ set(res.channel_txns))
        txn_bad += sum(not _same_txns(res.channel_txns[c], txns[c])
                       for c in set(txns) & set(res.channel_txns))
        if k in modelled:
            ref_fin[k] = {c: channel.simulate(t, mem)
                          for c, t in txns.items()}
            ref_dur[k] = max((f.max(initial=0.0)
                              for f in ref_fin[k].values()), default=0.0)

    # -- the answers held to them ------------------------------------------
    if answers == "control":
        log, got_fin, got_dur = _control_answers(log, ref_fin, ref_dur)
    else:
        got_fin = {k: {c: np.asarray(checked[k][2].channel_results[c]
                                     .finish_ns)
                       for c in checked[k][2].channel_results}
                   for k in ref_fin}
        got_dur = {k: log[k][5] for k in ref_dur}
    for k, want in ref_fin.items():
        for c, f in want.items():
            got = got_fin[k].get(c)
            sched_bad += (f.size if got is None or got.shape != f.shape
                          else int(np.count_nonzero(got != f)))
        sched_bad += got_dur[k] != ref_dur[k]
    for k, (_, _, res) in checked.items():
        if res.mode != "cycle":
            floor_bad += got_dur[k] < ref_dur[k]

    # -- every logged step: admissions, slots, lengths, clock --------------
    prompt, out = reqs.prompt_len, reqs.max_new_tokens
    arrival = reqs.arrival_ns
    bytes_bad = clock_bad = 0
    done = np.zeros(len(reqs), np.int64)
    live: dict[int, set] = {}
    last_admit: dict[int, int] = {}
    last_end: dict[int, tuple[float, bool]] = {}
    expect: dict[int, dict] = {}
    for k, (rep, admitted, active, finished, start, dur) in enumerate(log):
        cur = live.setdefault(rep, set())
        for rid in admitted:
            if rid <= last_admit.get(rep, -1):
                bytes_bad += 1
            last_admit[rep] = rid
            cur.add(rid)
        if len(active) > n_slots or not set(active) <= cur:
            bytes_bad += 1
        if k in checked:
            expect[k] = {rid: (pools * -(-int(prompt[rid] + done[rid])
                                         // page_tokens) * page_bytes,
                               pools * per_tok) for rid in active}
        for rid in active:
            done[rid] += 1
        for rid in finished:
            if done[rid] != out[rid]:
                bytes_bad += 1
            cur.discard(rid)
        # a busy replica steps on at its last end; an idle one (or a new
        # one, at 0) at the later of that and its newest admission
        prev_end, busy = last_end.get(rep, (0.0, False))
        want = (prev_end if busy or not admitted
                else max(prev_end, max(float(arrival[r]) for r in admitted)))
        if start != want or not (busy or admitted):
            clock_bad += 1
        last_end[rep] = (start + dur, bool(cur))

    for k, (_, st, res) in checked.items():
        _, nbytes, is_w, _, sid = cols[k]
        got: dict[int, list] = {}
        for r, n, w in zip(sid.tolist(), nbytes.tolist(), is_w.tolist()):
            tot = got.setdefault(r if r >= 0 else -1, [0, 0])
            tot[1 if w else 0] += n
        want = {rid: list(v) for rid, v in expect[k].items()}
        if weights:
            want[-1] = [weights, 0]
        bytes_bad += sum(got.get(r) != v for r, v in want.items())
        bytes_bad += len(set(got) - set(want))

    readings = {"bytes_bad": bytes_bad, "census_bad": census_bad,
                "clock_bad": clock_bad}
    if traffic["sim_mode"] != "analytic":
        readings |= {"txn_bad": txn_bad, "sched_bad": sched_bad}
    if traffic["sim_mode"] != "cycle":
        readings["floor_bad"] = floor_bad
    return {k: int(v) for k, v in readings.items()}


def _control_answers(log, ref_fin, ref_dur):
    """The reference's answers in float32: its finish times and step
    durations (the program's, where it computed none), and each
    replica's clock advanced by them."""
    f32 = np.float32
    fin = {k: {c: f.astype(f32).astype(np.float64) for c, f in v.items()}
           for k, v in ref_fin.items()}
    dur32 = {k: float(f32(v)) for k, v in ref_dur.items()}
    out, ends = [], {}
    for k, (rep, admitted, active, finished, start, dur) in enumerate(log):
        d = dur32.get(k, float(f32(dur)))
        prev = ends.get(rep)
        # a replica that went idle restarts at the next arrival
        s = (float(f32(start)) if prev is None or prev[1] != start
             else float(f32(prev[0])))
        out.append((rep, admitted, active, finished, s, d))
        ends[rep] = (s + d, start + dur)
    return out, fin, dur32
