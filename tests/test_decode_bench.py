"""``benchmarks/decode_bench.py``'s rungs: a smoke of the pre-cell harness
on the CPU mesh, in a file of its own because it is one subprocess of
half a minute that the driver's ``--dist loadfile`` should not queue
behind ``test_serving.py`` (``tests/README.md``)."""

import os


class TestDecodeBenchCI:
    def test_decode_rungs_emit_protocol_json_on_cpu_mesh(self, tmp_path):
        """Acceptance: ``decode_bs1``/``decode_saturated`` run on the
        8-virtual-device CPU mesh and print per-rung JSON carrying the
        min-of-N protocol fields plus the serving fingerprints (the
        ``decode_step`` budget verdict, the decode program's authored
        census + trace hash, capacity/page geometry).  Tiny shapes via
        the HUNT_* knobs: a smoke of the harness, not a measurement."""
        import json as _json
        import subprocess
        import sys

        from conftest import subprocess_env

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = subprocess_env(8)
        env.update({
            "HUNT_DECODE_TOKENS": "2", "HUNT_REPEATS": "1",
            "HUNT_DECODE_CAPACITY": "2", "HUNT_SERVE_DMODEL": "32",
            "HUNT_SERVE_LAYERS": "1", "HUNT_SERVE_HEADS": "4",
            "HUNT_SERVE_VOCAB": "64", "HUNT_SERVE_PROMPT": "4",
            "HUNT_SERVE_PAGE": "8",
        })
        proc = subprocess.run(
            [sys.executable,
             os.path.join(repo, "benchmarks", "decode_bench.py"),
             "--cpu-mesh"],
            env=env, capture_output=True, text=True, timeout=560,
            cwd=tmp_path,
        )
        assert proc.returncode == 0, (
            f"decode_bench exited {proc.returncode}\n"
            f"--- stdout ---\n{proc.stdout[-3000:]}\n"
            f"--- stderr ---\n{proc.stderr[-3000:]}"
        )
        recs = {}
        for line in proc.stdout.splitlines():
            if line.startswith("{"):
                r = _json.loads(line)
                assert "error" not in r, r
                recs[r["metric"]] = r
        want = {"decode_bs1_tokens_per_sec_per_chip",
                "decode_saturated_tokens_per_sec_per_chip",
                "decode_prefix_shared_tokens_per_sec_per_chip",
                "decode_prefix_cold_tokens_per_sec_per_chip",
                "decode_spec_k4_tokens_per_sec_per_chip",
                "decode_spec_off_tokens_per_sec_per_chip",
                "decode_disagg_on_tokens_per_sec_per_chip",
                "decode_disagg_off_tokens_per_sec_per_chip"}
        assert want <= set(recs), sorted(recs)
        for name in want:
            r = recs[name]
            # a noisy CI host can land every paired difference
            # non-positive: the bench then reports a DISCLOSED null
            # — never a negative rate
            if r["noise_floor"]:
                assert r["value"] is None
            else:
                assert r["value"] > 0
            assert r["unit"] == "tokens_per_sec_per_chip"
            assert r["n_measurements"] == 1  # HUNT_REPEATS
            # serving fingerprints: the budget pin's verdict rides
            # every row, so a capture where the program grew a
            # collective reads as a config change, not noise
            assert r["budget"] == "decode_step"
            assert r["budget_within"] is True
            # the CPU smoke serves the non-TP engine: zero authored
            # collectives (the census is {}), trivially within budget —
            # the trace hash still fingerprints the program
            assert r["decode_census"] == {}
            assert len(r["decode_trace_hash"]) == 12
            assert r["page_size"] == 8
        assert recs["decode_bs1_tokens_per_sec_per_chip"]["capacity"] == 1
        assert recs[
            "decode_saturated_tokens_per_sec_per_chip"]["capacity"] == 2
        # prefix-sharing A/B pair: the shared rung actually aliased
        # pages and fingerprints the distinct-page saving vs its own
        # cold leg; the cold rung shares nothing (deterministic serve,
        # so the two rungs' peaks reconcile exactly)
        shared = recs["decode_prefix_shared_tokens_per_sec_per_chip"]
        cold = recs["decode_prefix_cold_tokens_per_sec_per_chip"]
        assert shared["share_prefixes"] is True
        assert cold["share_prefixes"] is False
        assert shared["prefix_hits"] >= 1
        assert cold["prefix_hits"] == 0
        assert shared["pages_saved"] >= 1
        assert (shared["peak_used_pages"] + shared["pages_saved"]
                == cold["peak_used_pages"])
        # speculative A/B pair: the k=4 rung reports its acceptance
        # rate and the verify program's pinned budget verdict; the off
        # rung is the plain-decode control (no spec fields)
        spec = recs["decode_spec_k4_tokens_per_sec_per_chip"]
        assert spec["spec_k"] == 4
        assert 0.0 <= spec["acceptance_rate"] <= 1.0
        assert spec["verify_steps"] > 0
        assert spec["spec_budget"] == "spec_verify_step"
        assert spec["spec_budget_within"] is True
        assert spec["verify_census"] == {}  # non-TP smoke: authored 0
        assert len(spec["verify_trace_hash"]) == 12
        assert "spec_k" not in recs[
            "decode_spec_off_tokens_per_sec_per_chip"]
        # disaggregation A/B pair: the on rung serves the same mixed
        # stream through role pools and fingerprints the handoff
        # (codec, exact wire bytes, count) plus the prefill program's
        # own pinned budget; both legs split TTFT into queue/prefill
        don = recs["decode_disagg_on_tokens_per_sec_per_chip"]
        doff = recs["decode_disagg_off_tokens_per_sec_per_chip"]
        assert don["disagg"] is True
        assert doff["disagg"] is False
        assert don["handoff_codec"] == "bf16"
        assert doff["handoff_codec"] is None
        assert don["handoff_bytes"] > 0
        assert don["n_handoffs"] == 4  # 2 * HUNT_DECODE_CAPACITY
        for leg in (don, doff):
            assert leg["prefill_budget"] == "prefill_step"
            assert leg["prefill_budget_within"] is True
            assert leg["prefill_census"] == {}  # non-TP smoke
            for f in ("ttft_p50_ms", "ttft_p99_ms",
                      "ttft_queue_p50_ms", "ttft_prefill_p50_ms"):
                assert f in leg, f
        # the ingest phase only exists on the disaggregated leg
        assert "ingest_p50_ms" in don
        assert "ingest_p50_ms" not in doff
