"""The load generator, both loops, against a stub of the server's wire
protocol (chunked NDJSON, one line a token): no JAX, no model.  What a
cell's tails are made of is checked here: a request of the open loop is
timed from when it was due, the generator's lateness is reported, and a
refused request counts as failed and as beyond any value."""

import json
import socket
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

import harness
import loadgen

LENGTHS = {"prompt_tokens": {"dist": "loguniform", "min": 4, "max": 16},
           "output_tokens": {"dist": "loguniform", "min": 3, "max": 6},
           "schedule_size": 8, "warm_seconds": 0.2, "drain_seconds": 10,
           "check_requests": 3}


class Stub(ThreadingHTTPServer):
    daemon_threads = True
    token_seconds = 0.005
    refuse = False
    seen: list


class Handler(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"

    def setup(self):
        super().setup()     # a token line goes out when it is written
        self.request.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)

    def log_message(self, *args):
        pass

    def do_POST(self):
        body = json.loads(self.rfile.read(int(self.headers["Content-Length"])))
        self.server.seen.append(body)
        if self.server.refuse:
            self.send_response(503)
            self.send_header("Content-Length", "4")
            self.end_headers()
            self.wfile.write(b"busy")
            return
        self.send_response(200)
        self.send_header("Transfer-Encoding", "chunked")
        self.end_headers()
        rows = [{"token": (sum(body["tokens"]) + i) % 97, "index": i}
                for i in range(body["max_new_tokens"])] + [
                    {"done": "max_tokens"}]
        for row in rows:
            time.sleep(self.server.token_seconds)
            line = json.dumps(row).encode() + b"\n"
            self.wfile.write(b"%x\r\n%s\r\n" % (len(line), line))
            self.wfile.flush()
        self.wfile.write(b"0\r\n\r\n")


@pytest.fixture
def stub():
    server = Stub(("127.0.0.1", 0), Handler)
    server.seen = []
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    yield server
    server.shutdown()
    server.server_close()
    thread.join(5)


def offer(stub, tmp_path, capsys, traffic, seconds=1.0, seed=3_000_000_019):
    path = tmp_path / "traffic.json"
    path.write_text(json.dumps({**LENGTHS, **traffic}))
    assert loadgen.main(["--port", str(stub.server_address[1]), "--traffic",
                         str(path), "--seed", str(seed), "--seconds",
                         str(seconds), "--vocab", "101"]) == 0
    plan, result = map(json.loads, capsys.readouterr().out.splitlines())
    assert plan["event"] == "plan" and result["event"] == "result"
    assert (result["t0"], result["t1"]) == (plan["t0"], plan["t1"])
    assert plan["t1"] - plan["t0"] == pytest.approx(seconds)
    return result


def test_closed_loop_keeps_every_client_busy(stub, tmp_path, capsys):
    res = offer(stub, tmp_path, capsys, {"loop": "closed", "clients": 3})
    assert res["failed"] == 0 and res["attempted"] >= 3
    assert res["lateness_s"] is None          # no request was ever due
    assert len(res["ttft_s"]) == res["attempted"]
    assert all(0.004 < t < 0.5 for t in res["ttft_s"])
    assert res["tokens_in_window"] == pytest.approx(len(res["gap_s"]),
                                                    abs=res["requests_total"])
    assert harness.percentile(res["gap_s"], 50) == pytest.approx(0.005,
                                                                 abs=0.004)
    # the sample for the reference: the longest finished request leads it,
    # and each prompt is what the seed gives that request
    sample = res["sample"]
    assert len(sample) == 3
    sent = {tuple(b["tokens"]): b for b in stub.seen}
    for s in sample:
        assert len(s["tokens"]) == sent[tuple(s["prompt"])]["max_new_tokens"]
    longest = max(len(b["tokens"]) + b["max_new_tokens"] for b in stub.seen
                  if tuple(b["tokens"]) in {tuple(s["prompt"])
                                            for s in sample})
    assert len(sample[0]["prompt"]) + len(sample[0]["tokens"]) == longest
    assert all(b["temperature"] == 0.0 for b in stub.seen)


def test_open_loop_offers_what_the_file_says(stub, tmp_path, capsys):
    traffic = {"loop": "open", "rate_per_s": 20.0, "arrivals": "uniform",
               "burst": {"every_s": 0.5, "size": 4}, "max_in_flight": 16}
    res = offer(stub, tmp_path, capsys, traffic, seconds=1.0)
    # 20 a second for 1 s, and the bursts that fall into the window
    assert 20 <= res["attempted"] <= 20 + 3 * 4
    assert res["failed"] == 0
    assert res["lateness_s"]["max"] < 0.05
    assert all(0.004 < t < 0.2 for t in res["ttft_s"])
    assert res["requests_total"] > res["attempted"]   # the warm period's


def test_open_loop_times_a_request_from_when_it_was_due(stub, tmp_path,
                                                        capsys):
    # one connection for 40 requests a second that each take over 20 ms:
    # the generator falls behind, says so, and the wait is in the latency
    stub.token_seconds = 0.01
    traffic = {"loop": "open", "rate_per_s": 40.0, "arrivals": "uniform",
               "max_in_flight": 1}
    res = offer(stub, tmp_path, capsys, traffic, seconds=1.0)
    assert res["failed"] == 0
    assert res["lateness_s"]["max"] > 0.3
    assert max(res["ttft_s"]) > res["lateness_s"]["mean"]
    assert max(res["ttft_s"]) > 0.3


def test_a_refused_request_is_failed_and_beyond_any_value(stub, tmp_path,
                                                          capsys):
    stub.refuse = True
    res = offer(stub, tmp_path, capsys, {"loop": "closed", "clients": 2},
                seconds=0.3)
    assert res["attempted"] > 0 and res["failed"] == res["attempted"]
    assert all(t == float("inf") for t in res["ttft_s"])
    assert res["sample"] == [] and res["tokens_in_window"] == 0
    assert "503" in res["errors"][0] or "busy" in res["errors"][0]


def test_an_unknown_loop_is_an_error(stub, tmp_path, capsys):
    with pytest.raises(SystemExit):
        offer(stub, tmp_path, capsys, {"loop": "spiral"})
