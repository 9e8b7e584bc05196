"""The benchmark's tables, read as files (no server, no JAX): what the
driver refuses before or after a run and no chip run is needed to see.

PR 41's new long-context closed-loop cell was refused ``output_malformed``:
``attempted`` (the requests DUE inside the window, ``benchmarks/run.py``) was
0 in every run. In a closed loop a request is due when its client is free,
the first wave is due in the lead-in, and no first-wave stream ended before
the window closed: outputs too long for the step. A closed-loop mix therefore
records what it was sized for (``sized_for``: the step a token takes a stream
as the client sees it, the seconds the first wave decodes before the window
opens, the seconds a rejoin takes before its first token), and this file
replays ``traffic.plan`` against that on 8 seeds: some first-wave stream ends
inside the window (as many as the mix says), none before it, and the ready
requests do not run out. ``traffic/decode-sat.json`` and
``traffic/longctx-sat.json`` record no ``sized_for`` yet (a ``benchmark``
issue's to add: a PR may not edit a file the benchmark has).
"""

import json
from pathlib import Path

import pytest

from benchmarks import traffic
from benchmarks.config import server_flag

ROOT = Path(__file__).resolve().parents[1]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
SEEDS = (1, 77, 2400000101, 2147483659, 3100000301, 3900000602, 4000000101, 4200000007)


def _load(cell: dict):
    config = json.loads((ROOT / next(
        c["file"] for c in BENCH["configs"] if c["name"] == cell["config"])).read_text())
    mix = json.loads((ROOT / f"benchmarks/traffic/{cell['traffic']}.json").read_text())
    load = json.loads((ROOT / f"benchmarks/cells/{cell['name']}.json").read_text())
    return config, mix, load


def _sized(cell: dict, key: str = "first_wave_ends") -> bool:
    """A closed-loop mix that records what it was sized for: long answers,
    of which the FIRST WAVE's end inside the window (``first_wave_ends``), or
    short ones, whose clients turn over several times (``ends_in_window``)."""
    mix = _load(cell)[1]
    return mix.get("arrivals") == "closed" and key in mix.get("sized_for", {})


SIZED = [c["name"] for c in BENCH["workloads"] if _sized(c)]


def replay(planned, clients: int, lead: float, seconds: float, sized: dict):
    """``(ends of the first wave, requests taken by the window's end)`` of a
    closed loop that decodes as ``sized`` says: the first wave starts
    decoding together ``decode_s_before_window`` before the window, a token
    takes a stream ``step_ms``, a rejoin ``join_s`` before its first."""
    step = sized["step_ms"] / 1e3
    t0 = lead - sized["decode_s_before_window"]
    first = [t0 + r.output_tokens * step for r in planned[:clients]]
    free, taken = sorted(first), clients
    while free and free[0] < lead + seconds:
        t = free.pop(0)
        if taken >= len(planned):
            return first, len(planned) + 1
        free.append(t + sized.get("join_s", 0.0) + planned[taken].output_tokens * step)
        free.sort()
        taken += 1
    return first, taken


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name", SIZED)
def test_a_sized_closed_loop_cell_has_requests_due_in_its_window(name, seed):
    cell = next(c for c in BENCH["workloads"] if c["name"] == name)
    config, mix, load = _load(cell)
    seconds = float(BENCH["run_seconds"])
    planned, lead = traffic.plan(
        mix, load, seconds, seed, config["vocab_size"],
        int(server_flag(config, "--max-seq", 4096)))
    clients = int(load["clients"])
    assert clients == int(server_flag(config, "--concurrent"))
    first, taken = replay(planned, clients, lead, seconds, mix["sized_for"])
    assert min(first) >= lead, "a first-wave stream ends in the lead-in"
    in_window = sum(lead <= t < lead + seconds for t in first)
    lo, hi = mix["sized_for"]["first_wave_ends"]
    # `attempted` counts the requests due in the window: each end frees a client
    assert 1 <= lo <= in_window <= hi, (in_window, sorted(first))
    assert taken <= len(planned), "the mix's ready requests would run out"


TURNING = [c["name"] for c in BENCH["workloads"] if _sized(c, "ends_in_window")]


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name", TURNING)
def test_a_cell_whose_clients_turn_over_has_ends_in_its_window(name, seed):
    """``chatgen-sat``: answers of 1-1.5k tokens at a token time under 20 ms,
    so a client ends several requests in the lead-in and the window. The
    replay of ``traffic.plan`` at the recorded token time: as many requests
    END inside the window as the mix says (each frees a client: ``attempted``),
    and the ready requests do not run out — nor at a token time HALF the
    recorded one (a later PR's faster program)."""
    cell = next(c for c in BENCH["workloads"] if c["name"] == name)
    config, mix, load = _load(cell)
    seconds, sized = float(BENCH["run_seconds"]), mix["sized_for"]
    planned, lead = traffic.plan(
        mix, load, seconds, seed, config["vocab_size"],
        int(server_flag(config, "--max-seq", 4096)))
    clients = int(load["clients"])
    assert clients == int(server_flag(config, "--concurrent"))

    def ends(step_ms):
        step, t0 = step_ms / 1e3, lead - sized["decode_s_before_window"]
        free = sorted(t0 + r.output_tokens * step for r in planned[:clients])
        taken, out = clients, []
        while free and free[0] < lead + seconds:
            out.append(free.pop(0))
            if taken >= len(planned):
                return out, len(planned) + 1
            free.append(out[-1] + sized["join_s"] + planned[taken].output_tokens * step)
            free.sort()
            taken += 1
        return out, taken

    out, taken = ends(sized["step_ms"])
    lo, hi = sized["ends_in_window"]
    assert lo <= sum(lead <= t < lead + seconds for t in out) <= hi
    assert taken <= len(planned), "the mix's ready requests would run out"
    assert ends(sized["step_ms"] / 2)[1] <= len(planned), "... at half the token time"


def test_the_chatgen_cell_is_sized_and_fits_its_pool():
    name = "sdar-30b-a3b-bf16-ep16.chatgen-sat"
    assert name in TURNING and name not in SIZED
    config, mix, load = _load(next(c for c in BENCH["workloads"] if c["name"] == name))
    # a join is one prefill chunk, and prompt + answer fits a slot's pages,
    # every slot's at once, to the row
    chunk = int(server_flag(config, "--prefill-chunk"))
    assert mix["prompt_tokens"]["max"] <= chunk
    longest = mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"]
    assert longest == int(server_flag(config, "--max-seq"))
    assert int(load["clients"]) * -(-longest // chunk) == int(server_flag(config, "--paged-pool"))
    # the check's prompts start the first decode block with two prompt tokens
    block = config["block_length"]
    assert [p % block for p in config["bench"]["check"]["prompt_tokens"]] == [2, 2]
    assert config["mask_token_id"] == 0  # an id the harness never draws


def test_the_new_long_context_cell_is_sized():
    assert "zaya1-8b-bf16-pp2ep2.longctx8k-sat" in SIZED


def test_the_reason_cell_is_sized_for_8_to_16_rejoins():
    assert "granite4-h-micro-bf16.reason-sat" in SIZED
    mix = _load(next(c for c in BENCH["workloads"]
                     if c["name"] == "granite4-h-micro-bf16.reason-sat"))[1]
    assert mix["sized_for"]["first_wave_ends"] == [8, 16]
    # every rejoin is one prefill chunk, and prompt + answer fits a slot's pages
    assert mix["prompt_tokens"]["max"] <= 512
    assert mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"] <= 4608


def test_the_reason1k_cell_is_sized_for_8_to_16_rejoins():
    name = "kimi-linear-48b-bf16-ep16.reason1k-sat"
    assert name in SIZED
    config, mix, load = _load(next(c for c in BENCH["workloads"] if c["name"] == name))
    assert mix["sized_for"]["first_wave_ends"] == [8, 16]
    assert mix["output_tokens"]["max"] - mix["output_tokens"]["min"] == 2304
    # a join is two or three prefill chunks, and prompt + answer fits a
    # slot's pages, every slot's at once
    chunk = int(server_flag(config, "--prefill-chunk"))
    assert chunk < mix["prompt_tokens"]["min"] and mix["prompt_tokens"]["max"] <= 3 * chunk
    longest = mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"]
    assert longest <= int(server_flag(config, "--max-seq"))
    assert int(load["clients"]) * -(-longest // chunk) <= int(server_flag(config, "--paged-pool"))


def test_the_longgen_cell_is_sized_for_8_to_16_rejoins():
    name = "qwen3-next-80b-bf16-ep4.longgen-sat"
    assert name in SIZED
    config, mix, load = _load(next(c for c in BENCH["workloads"] if c["name"] == name))
    assert mix["sized_for"]["first_wave_ends"] == [8, 16]
    assert (mix["prompt_tokens"]["min"], mix["prompt_tokens"]["max"]) == (1024, 1536)
    # a join is two or three prefill chunks, and prompt + answer fits a
    # slot's pages, every slot's at once
    chunk = int(server_flag(config, "--prefill-chunk"))
    assert -(-mix["prompt_tokens"]["min"] // chunk) == 2
    assert -(-mix["prompt_tokens"]["max"] // chunk) == 3
    longest = mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"]
    assert longest <= int(server_flag(config, "--max-seq"))
    assert int(load["clients"]) * -(-longest // chunk) <= int(server_flag(config, "--paged-pool"))
    # a first-wave stream's second request cannot end inside the window
    step = mix["sized_for"]["step_ms"] / 1e3
    assert 2 * mix["output_tokens"]["min"] * step > mix["lead_in_s"] + BENCH["run_seconds"]


def test_the_shortchat_cell_is_sized_and_fits_its_pool():
    """``olmo-hybrid-7b-bf16-pp2.shortchat-sat``: clients that turn over
    (``ends_in_window``, replayed on 8 seeds above), one prefill chunk a join,
    prompt + answer at most the 1024 tokens a slot's two pages hold, every
    slot's at once, to the page; ``--max-seq`` is a page longer, for the
    check's three-chunk prompt alone."""
    name = "olmo-hybrid-7b-bf16-pp2.shortchat-sat"
    assert name in TURNING and name not in SIZED
    config, mix, load = _load(next(c for c in BENCH["workloads"] if c["name"] == name))
    chunk = int(server_flag(config, "--prefill-chunk"))
    assert int(load["clients"]) == int(server_flag(config, "--concurrent")) == 48
    assert mix["prompt_tokens"]["max"] <= chunk
    longest = mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"]
    assert longest == 1024 == 2 * chunk
    assert int(load["clients"]) * 2 == int(server_flag(config, "--paged-pool"))
    check = config["bench"]["check"]
    # prompts of one and of three chunks, each with its answer inside --max-seq
    assert [-(-p // chunk) for p in check["prompt_tokens"]] == [1, 3]
    assert max(check["prompt_tokens"]) + check["generate"] <= int(server_flag(config, "--max-seq"))
    assert int(server_flag(config, "--max-seq")) == 3 * chunk
    pool = config["bench"]["pool"]
    assert pool["bytes"] == (pool["pages"] + 1) * pool["page_tokens"] * pool["kv_layers"] * 15360
    assert pool["state_bytes"] == 49 * 12 * (2211840 + 69120)
    assert set(check["controls"]) <= {
        "gdn_state_reset", "beta_unscaled", "qk_norm_per_head", "linear_prenorm",
        "rope_on", "weights_fp8"}


def _texts():
    for kind in ("configs", "workloads"):
        for entry in BENCH[kind]:
            for key in ("why", "source"):
                if key in entry:
                    yield f"{kind}:{entry['name']}:{key}", entry[key]
    for i, word in enumerate(BENCH["command"]):
        yield f"command:{i}", word
    for m in BENCH["per_layer"]:
        yield f"per_layer:{m['name']}:layer", m["layer"]


@pytest.mark.parametrize("where,text", list(_texts()), ids=[w for w, _ in _texts()])
def test_every_line_of_the_table_is_1_to_200_printable_characters(where, text):
    assert isinstance(text, str) and 1 <= len(text) <= 200, (where, len(text))
    assert text.isprintable() and "\t" not in text and "\n" not in text, where


def test_greedy_blocks_share_reads_the_sampler_classes_of_the_window():
    """``mst_decode_blocks_total{sampler}`` between the two scrapes; a
    program from before the counter (the parent of the PR that added it)
    exposes nothing and the metric is left out, as in an empty window."""
    from benchmarks.run import load_reader

    read = load_reader("layer_metrics", "greedy_blocks_share")
    family = 'mst_decode_blocks_total{sampler="%s"}'
    before = {family % "greedy": 40.0, family % "draw": 1.0, family % "nucleus": 2.0,
              "mst_decode_blocks_dispatched_total": 43.0}
    after = {family % "greedy": 340.0, family % "draw": 26.0, family % "nucleus": 77.0,
             "mst_decode_blocks_dispatched_total": 443.0}
    assert read({"before": before, "after": after}) == pytest.approx(75.0)
    all_greedy = {**before, family % "greedy": 440.0}
    assert read({"before": before, "after": all_greedy}) == 100.0
    assert read({"before": before, "after": before}) is None
    old = {"mst_decode_blocks_dispatched_total": 443.0}
    assert read({"before": old, "after": old}) is None
    assert read({"before": None, "after": None}) is None


def test_join_programs_mean_reads_the_joins_dispatches_of_the_window():
    """``mst_join_programs_total{program}`` over ``mst_join_seconds_count``
    between the two scrapes; a program from before the counter (the parent
    of the PR that added it) exposes nothing and the metric is left out, as
    in a window in which no join reached decode."""
    from benchmarks.run import load_reader

    read = load_reader("layer_metrics", "join_programs.mean")
    family = 'mst_join_programs_total{program="%s"}'
    before = {family % "claim": 40.0, family % "chunk": 41.0, family % "finish": 40.0,
              family % "other": 0.0, "mst_join_seconds_count": 40.0}
    one_chunk = {family % "claim": 240.0, family % "chunk": 241.0, family % "finish": 240.0,
                 family % "other": 0.0, "mst_join_seconds_count": 240.0}
    assert read({"before": before, "after": one_chunk}) == pytest.approx(3.0)
    sixteen = {family % "claim": 50.0, family % "chunk": 201.0, family % "finish": 50.0,
               family % "other": 0.0, "mst_join_seconds_count": 50.0}
    assert read({"before": before, "after": sixteen}) == pytest.approx(18.0)
    assert read({"before": before, "after": before}) is None  # no join in the window
    old = {"mst_join_seconds_count": 240.0}
    assert read({"before": {"mst_join_seconds_count": 40.0}, "after": old}) is None
    assert read({"before": None, "after": None}) is None


def test_emit_hold_ms_mean_reads_the_holds_of_the_window():
    """``mst_emit_hold_seconds_sum`` over its ``_count`` between the two
    scrapes, in milliseconds; a program from before the counter (the parent
    of the PR that added it) exposes nothing and the metric is left out, as
    in a window that held nothing."""
    from benchmarks.run import load_reader

    entry = next(m for m in BENCH["per_layer"] if m["name"] == "emit_hold_ms.mean")
    # no ``workloads`` list: every cell reports it
    assert entry == {"name": "emit_hold_ms.mean", "unit": "ms", "better": "lower",
                     "source": "program_counter", "layer": "scheduler",
                     "moves": "out_tok_s"}
    read = load_reader("layer_metrics", "emit_hold_ms.mean")
    before = {"mst_emit_hold_seconds_sum": 0.05, "mst_emit_hold_seconds_count": 40.0,
              'mst_emit_held_total{flush="chunk"}': 9000.0}
    after = {"mst_emit_hold_seconds_sum": 0.45, "mst_emit_hold_seconds_count": 240.0,
             'mst_emit_held_total{flush="chunk"}': 58000.0}
    assert read({"before": before, "after": after}) == pytest.approx(2.0)
    assert read({"before": before, "after": before}) is None  # no hold in the window
    old = {"mst_join_seconds_count": 240.0}
    assert read({"before": old, "after": old}) is None
    assert read({"before": None, "after": None}) is None
