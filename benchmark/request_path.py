"""A request's path to its first token, step by step: what the seven
readers of ``layer_metrics/`` that explain ``ttft_per_token_p50_ms`` and the
tail of ``itl_p95_ms`` share.

Two records of a traced run, joined by the loop's ``step`` ordinal and never
by converting clocks (the profiler lays the device's clock against the
host's anew in every session; an ordinal does not drift):

* the request spans of ``paddle_tpu/obs/trace.py`` (``obs["spans"]``, on
  ``time.time()``): a ``slot`` span starts at the seat with ``step`` (the
  first device step that can carry the request), ``prompt_tokens``, ``chunk``
  and ``teacher_forced``; every ``prefill_chunk`` event says the ``step`` that
  fed it, every ``prefill_stall`` the ``step`` that left the row out, and
  ``first_token`` the ``of_step`` whose read produced the token;
* the loop's phases (``host_spans``, on the profiler's clock): the
  ``engine.step.dispatch`` phase of ``step`` n says what the step carried
  (``rows``, ``prefill_rows``), ``engine.step.wait`` and ``gen.loop.emit``
  say whose tokens they handle in ``of_step``.

A program from before the stamps gives spans without ``step`` and phases
without ``prefill_rows``: every function here then returns None, and so does
every reader, so the line of such a run leaves the metric out."""

from benchmark import arith, host_spans


def _event(span, name):
    """The first event ``name`` of a span dict, or None."""
    return next((e for e in span.get("events", ()) if e["name"] == name),
                None)


def _in_window(obs, t):
    w_open, w_close = obs["window_wall"]
    return w_open <= t < w_close


def steps_needed(teacher_forced, chunk):
    """Device steps with a prompt chunk that a feed of ``teacher_forced``
    tokens takes at a whole chunk a step: lane 0 holds the row's current
    token and the chunk arms lanes 1..n, so a step consumes ``n + 1`` of the
    feed, ``chunk`` at most, and the last one whatever is left."""
    return -(-int(teacher_forced) // int(chunk))


def prefills(obs):
    """The fresh admissions seated inside the window that reached their
    first token, one dict each: ``seconds`` (seat to first token),
    ``prompt_tokens``, ``needed`` (``steps_needed``) and ``taken`` (the first
    step that fed the row or left it out, to its last chunk's).  None where
    no ``slot`` span carries the stamps."""
    rows, stamped = [], False
    for s in obs.get("spans") or ():
        a = s.get("attrs", {})
        if s["name"] != "slot" or "step" not in a \
                or "prompt_tokens" not in a:
            continue
        stamped = True
        first = _event(s, "first_token")
        if a.get("mode") != "prefill" or first is None \
                or not _in_window(obs, s["t_start"]):
            continue
        fed = [e["attrs"]["step"] for e in s["events"]
               if e["name"] == "prefill_chunk"]
        out = [e["attrs"]["step"] for e in s["events"]
               if e["name"] == "prefill_stall"]
        rows.append({
            "seconds": first["t"] - s["t_start"],
            "prompt_tokens": a["prompt_tokens"],
            "needed": steps_needed(a["teacher_forced"], a["chunk"])
            if fed else 0,
            "taken": max(fed) - min(fed + out) + 1 if fed else 0})
    return rows if stamped else None


def first_token_steps(obs):
    """{trace_id: (of_step, t)} of every request whose ``slot`` span's
    ``first_token`` says the step that produced it.  None where none
    does."""
    out = {}
    for s in obs.get("spans") or ():
        first = _event(s, "first_token") if s["name"] == "slot" else None
        if first and "of_step" in first.get("attrs", {}):
            out.setdefault(s["trace_id"],
                           (first["attrs"]["of_step"], first["t"]))
    return out or None


def first_token_tails(obs):
    """Seconds, on the profiler's clock, from the start of
    ``engine.step.dispatch`` n to the end of the ``gen.loop.emit`` whose
    ``of_step`` is n, for every n that produced some request's first token
    and whose dispatch starts inside the trace's window.  None without a
    device trace or without the stamps."""
    firsts, hs = first_token_steps(obs), host_spans.load(obs)
    if not firsts or not hs:
        return None
    steps = {n for n, _t in firsts.values()}
    start = {st.get("step"): s
             for s, _e, st in hs.phases.get("engine.step.dispatch", ())
             if hs.lo <= s < hs.hi}
    return [e - start[st["of_step"]]
            for _s, e, st in hs.phases.get("gen.loop.emit", ())
            if st.get("of_step") in steps and st["of_step"] in start]


def front_seconds(obs):
    """Per request, by ``trace_id``, what the front adds round the engine:
    handler start to the enqueue (``server.request`` start to
    ``gen.queue_wait`` start) plus the hand-over of the first token to the
    handler thread and its write (the server's ``first_token`` event less
    the slot's, both on ``time.time()``).  Requests that started inside the
    window and whose slot event carries ``of_step``; None where none does."""
    firsts = first_token_steps(obs)
    if not firsts:
        return None
    server, queued = {}, {}
    for s in obs["spans"]:
        if s["name"] == "server.request":
            server[s["trace_id"]] = s
        elif s["name"] == "gen.queue_wait":
            queued.setdefault(s["trace_id"], s["t_start"])
    out = []
    for tid, (_n, t_slot) in firsts.items():
        req = server.get(tid)
        wrote = _event(req, "first_token") if req else None
        if wrote and tid in queued and _in_window(obs, req["t_start"]):
            out.append((queued[tid] - req["t_start"])
                       + (wrote["t"] - t_slot))
    return out


def dispatches(obs):
    """The stats of every ``engine.step.dispatch`` that starts inside the
    trace's window and says its ``prefill_rows``.  None without a device
    trace or where none does."""
    hs = host_spans.load(obs)
    if not hs:
        return None
    return [st for s, _e, st in hs.phases.get("engine.step.dispatch", ())
            if hs.lo <= s < hs.hi and "prefill_rows" in st] or None


def step_intervals(obs):
    """[(seconds, rows, prefill_rows)] of every device step n of
    ``dispatches`` whose tokens AND step n - 1's were read: the interval
    between the two ``engine.step.wait`` ends (the gap the engine hands its
    decoding streams, before the front), beside what step n carried.  None
    as above."""
    carried = dispatches(obs)
    if not carried:
        return None
    read = {st.get("of_step"): e for _s, e, st
            in host_spans.load(obs).phases.get("engine.step.wait", ())}
    return [(read[st["step"]] - read[st["step"] - 1], st["rows"],
             st["prefill_rows"])
            for st in sorted(carried, key=lambda st: st["step"])
            if st["step"] in read and st["step"] - 1 in read]


def percentile_ms(seconds, q):
    """``arith.percentile`` of a list of seconds, in ms; None of None or of
    nothing."""
    p = arith.percentile(seconds or (), q)
    return None if p is None else p * 1e3
