"""The traffic generator and the SSE client."""

import socket
import threading
import time

import numpy as np

from benchmark import loadgen, traffic_gen

CHAT = traffic_gen.load_mix("chat-steady")
BATCH = traffic_gen.load_mix("batch-decode")


def take(mix, seed, n, seconds=51.0):
    gen = traffic_gen.request_stream(mix, seed, vocab=1000, seconds=seconds)
    return [next(gen) for _ in range(n)]


def test_same_seed_same_schedule_and_tokens():
    assert take(CHAT, 2_500_000_000, 130) == take(CHAT, 2_500_000_000, 130)
    assert take(CHAT, 1, 10) != take(CHAT, 2, 10)


def test_every_seed_gets_the_same_work_in_another_order():
    rate, lead = CHAT["rate_per_s"], CHAT["lead_s"]
    n_lead, n_win = round(rate * lead), round(rate * 51.0)

    def windows(seed):
        reqs = take(CHAT, seed, n_lead + 2 * n_win)
        due, out = 0.0, []
        for r in reqs:
            due += r["gap_s"]
            out.append((due, len(r["prompt"]), r["max_tokens"]))
        return out

    free = {k: v for k, v in CHAT.items() if k != "order_seed"}

    def windows(seed, mix=free):        # noqa: F811 — order left to the seed
        reqs = take(mix, seed, n_lead + 2 * n_win)
        due, out = 0.0, []
        for r in reqs:
            due += r["gap_s"]
            out.append((due, len(r["prompt"]), r["max_tokens"]))
        return out

    a, b = windows(7), windows(3_000_000_000)
    for lo, hi, n in ((0.0, lead, n_lead), (lead, lead + 51.0, n_win),
                      (lead + 51.0, lead + 102.0, n_win)):
        ina = [x for x in a if lo <= x[0] < hi]
        inb = [x for x in b if lo <= x[0] < hi]
        assert len(ina) == len(inb) == n        # the same count in a window
        assert sorted(x[1] for x in ina) == sorted(x[1] for x in inb)
        assert sorted(x[2] for x in ina) == sorted(x[2] for x in inb)
    assert [x[1] for x in a] != [x[1] for x in b]      # in another order
    # the mix as committed pins the order: only the token ids differ
    assert windows(7, CHAT) == windows(3_000_000_000, CHAT)
    assert take(CHAT, 7, 3)[0]["prompt"] != take(CHAT, 8, 3)[0]["prompt"]


def test_a_closed_loop_draws_blocks_of_the_same_lengths():
    free = {k: v for k, v in BATCH.items() if k != "order_seed"}
    a, b = take(free, 1, 128), take(free, 2, 128)
    assert all(r["gap_s"] == 0 for r in a)
    for lo in (0, 64):
        assert (sorted(len(r["prompt"]) for r in a[lo:lo + 64])
                == sorted(len(r["prompt"]) for r in b[lo:lo + 64]))
        assert (sorted(r["max_tokens"] for r in a[lo:lo + 64])
                == sorted(r["max_tokens"] for r in b[lo:lo + 64]))


def test_lengths_follow_the_mix_and_its_clips():
    plens = traffic_gen.quantile_lengths(CHAT["prompt_len"], 64)
    olens = traffic_gen.quantile_lengths(CHAT["output_len"], 64)
    assert plens.min() >= 32 and plens.max() <= 2048
    assert olens.min() >= 8 and olens.max() <= 512
    assert abs(float(np.median(plens)) - 384) < 20
    assert abs(float(np.median(olens)) - 128) < 8
    b = traffic_gen.quantile_lengths(BATCH["prompt_len"], 64)
    assert b.min() >= 128 and b.max() <= 256
    assert traffic_gen.prompt_buckets(CHAT) == [64, 128, 256, 512, 1024,
                                                2048]
    assert traffic_gen.prompt_buckets(BATCH) == [256]


def test_burst_gaps_keep_the_rate():
    calm = traffic_gen.exponential_gaps(64, 4.0)
    burst = traffic_gen.exponential_gaps(64, 4.0, cv=3.0)
    assert abs(calm.sum() - 16.0) < 1e-9 and abs(burst.sum() - 16.0) < 1e-9
    assert burst.std() / burst.mean() > 2 * calm.std() / calm.mean()


def test_train_batches_repeat_for_a_seed():
    mix = traffic_gen.load_mix("pretrain-4k")
    a = next(traffic_gen.train_batches(mix, 2**31 + 5, 32256, 2))
    b = next(traffic_gen.train_batches(mix, 2**31 + 5, 32256, 2))
    assert a.shape == (2, 4096) and a.dtype == np.int32
    assert (a == b).all() and a.max() < 32256


def _fake_sse_server(n_tokens, stall_s):
    """Answers each POST with ``n_tokens`` events; handles one request at
    a time and stalls before answering, so later requests wait."""
    srv = socket.socket()
    srv.bind(("127.0.0.1", 0))
    srv.listen(16)

    def serve():
        while True:
            try:
                conn, _ = srv.accept()
            except OSError:
                return
            conn.recv(65536)
            time.sleep(stall_s)
            conn.sendall(b"HTTP/1.1 200 OK\r\ncontent-type: "
                         b"text/event-stream\r\n\r\n")
            for i in range(n_tokens):
                conn.sendall(b"data: %d\n\n" % i)
                time.sleep(0.01)
            conn.sendall(b"data: [DONE]\n\n")
            conn.close()

    threading.Thread(target=serve, daemon=True).start()
    return srv


def test_open_loop_times_each_request_from_when_it_was_due():
    srv = _fake_sse_server(n_tokens=3, stall_s=0.2)
    reqs = iter([{"i": i, "gap_s": 0.05, "prompt": [1, 2], "max_tokens": 3}
                 for i in range(100)])
    start = time.monotonic() + 0.05
    streams = loadgen.open_loop_sse(
        reqs, host="127.0.0.1", port=srv.getsockname()[1], path="/x",
        temperature=0.0, start_at=start, stop_sending_at=start + 0.22,
        drain_s=10.0)
    srv.close()
    assert len(streams) == 4 and all(s.ok for s in streams)
    assert [s.tokens for s in streams] == [[0, 1, 2]] * 4
    # an open loop: all four were sent on schedule although the server
    # answers one at a time ...
    assert all(0 <= s.sent - s.due < 0.04 for s in streams)
    dues = [s.due - start for s in streams]
    assert all(abs(d - 0.05 * (i + 1)) < 1e-6 for i, d in enumerate(dues))
    # ... so the wait a stall imposes on later requests is counted
    ttft = [s.token_times[0] - s.due for s in streams]
    assert ttft[0] >= 0.2 and ttft[3] >= 0.2 * 4 - 0.15 - 0.01
    assert ttft == sorted(ttft)


def test_a_refused_request_counts_as_failed():
    srv = socket.socket()
    srv.bind(("127.0.0.1", 0))
    srv.listen(4)

    def serve():
        conn, _ = srv.accept()
        conn.recv(65536)
        conn.sendall(b"HTTP/1.1 503 Service Unavailable\r\n"
                     b"content-length: 4\r\n\r\nbusy")
        conn.close()

    threading.Thread(target=serve, daemon=True).start()
    start = time.monotonic()
    streams = loadgen.open_loop_sse(
        iter([{"i": 0, "gap_s": 0.0, "prompt": [1], "max_tokens": 2},
              {"i": 1, "gap_s": 9.0, "prompt": [1], "max_tokens": 2}]),
        host="127.0.0.1", port=srv.getsockname()[1], path="/x",
        temperature=0.0, start_at=start, stop_sending_at=start + 0.1,
        drain_s=5.0)
    srv.close()
    assert len(streams) == 1 and not streams[0].ok
    assert streams[0].status == 503


def test_percentile_is_nearest_rank():
    vals = list(range(1, 101))
    assert loadgen.percentile(vals, 95) == 95
    assert loadgen.percentile(vals, 50) == 50
    assert loadgen.percentile([5.0], 95) == 5.0
