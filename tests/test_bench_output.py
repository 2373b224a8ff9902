"""The bench's FINAL output line must stay inside the driver's capture
window and parse as JSON.  Round 4's record (`BENCH_r04.json`) was
`"parsed": null` because the single output line outgrew the ~2000-char
tail the driver keeps; `compact_headline` is the guard that can never
regress that way again."""

import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import bench  # noqa: E402


def _fat_detail():
    """A detail dict sized like the real round-4 output (the one that
    broke the capture window): every config present, long prose fields."""
    configs = {
        "text": {"frames_per_sec": 1891.2, "p50_ms": 1.174,
                 "p50_arrival_ms": 1.062, "drain_per_frame_ms": 0.112,
                 "vs_reference_broker_ceiling": 37.8},
        "asr": {"frames_per_sec_chip": 43.07, "audio_sec_per_sec": 861.4,
                "p50_ms": 26.04, "p50_arrival_ms": 2.62,
                "drain_per_frame_ms": 23.42, "model": "whisper_small",
                "batch": 4, "mfu": 0.026},
        "detector": {"frames_per_sec_chip": 73.62,
                     "images_per_sec": 1177.9, "p50_ms": 10.88,
                     "p50_arrival_ms": 0.34, "drain_per_frame_ms": 10.54,
                     "model": "yolov8n 640x640", "batch": 16,
                     "mfu": 0.0134},
        "llm": {"model": "llama32_1b (1236M params)", "batch": 4,
                "prompt_len": 128, "time_to_first_token_ms": 116.6,
                "tokens_per_sec": 481.0,
                "tokens_per_sec_by_batch": {"batch_16": 1598.5,
                                            "batch_64": 3430.0},
                "decode_mfu": 0.0061},
        "llm_sharded": {"tokens_per_sec": 10.7,
                        "collectives_per_decode_step": 2,
                        "collective_kinds": ["all-reduce"],
                        "mesh": "virtual 8-device CPU (data=2, model=4)",
                        "model": ("llama32_1b architecture at reduced "
                                  "width (16 layers, 32/8 GQA heads, "
                                  "tied embeddings)")},
        "train": {"model": "llama32_1b architecture, 8 layers (749M)",
                  "batch": 4, "seq_len": 1024, "tokens_per_sec": 16914.0,
                  "step_ms": 242.2, "train_mfu": 0.386,
                  "loss_finite": True},
        "longcontext": {"model": "llama32_1b architecture, 8 layers",
                        "batch": 1,
                        "prefill": {"seq_4096": {"tokens_per_sec": 23518.0,
                                                 "prefill_ms": 174.2,
                                                 "mfu": 0.1322},
                                    "seq_16384": {"tokens_per_sec": 8445.4,
                                                  "prefill_ms": 1940.0,
                                                  "mfu": 0.0647}}},
        "serving": {"streams": 32, "frames_per_sec_total": 591.5,
                    "coalesced_trials": [591.5, 1030.2, 1895.8, 1766.4,
                                         1820.9],
                    "coalesced_spread": [591.5, 1895.8],
                    "frames_per_sec_uncoalesced": 1617.2,
                    "uncoalesced_trials": [1617.2, 1084.3, 1216.9,
                                           1153.0, 1201.4],
                    "uncoalesced_spread": [1084.3, 1617.2],
                    "coalescing_speedup": 0.37, "trials_per_arm": 5,
                    "micro_batch": 16,
                    "model": "yolov8n 640x640",
                    "vs_reference_broker_ceiling": 11.8, "mfu": 0.0067},
        "latency": {"frames_per_sec_chip": 11.2, "p50_ms": 96.4,
                    "p50_arrival_ms": 92.1, "drain_per_frame_ms": 4.3,
                    "audio_seconds_per_frame": 5.0, "rows_per_frame": 2,
                    "micro_batch": 1, "frame_window": 1,
                    "operating_point": "latency (one frame in flight)",
                    "stages": ("whisper_small -> (text, llama32_1b "
                               "decode -> reply text) + yolov8n-640 -> "
                               "detections"),
                    "mfu": 0.011},
        "tts": {"frames_per_sec_chip": 24.55, "p50_ms": 132.4,
                "p50_arrival_ms": 1.13, "drain_per_frame_ms": 131.27,
                "audio_seconds_per_frame": 25.8,
                "speech_sec_per_sec": 633.3, "batch": 8, "mfu": 0.0032},
        "pipeline_multimodal": {
            "frames_per_sec_chip": 6.94, "p50_ms": 447.15,
            "p50_arrival_ms": 443.46, "drain_per_frame_ms": 3.7,
            "audio_seconds_per_frame": 5.0, "rows_per_frame": 16,
            "audio_realtime_factor": 555.32,
            "tokens_generated_per_frame": 512,
            "stages": ("whisper_small -> (text, llama32_1b decode -> "
                       "reply text) + yolov8n-640 -> detections"),
            "micro_batch": 4, "mfu": 0.0964},
    }
    return {
        "metric": "multimodal_pipeline_frames_per_sec",
        "value": 6.94,
        "unit": ("frames/sec end-to-end (3-stage speech+LM+vision graph, "
                 "HBM-resident, 1 chip)"),
        "vs_baseline": 92.55,
        "baseline": ("reference whisper-small single-GPU speech stage at "
                     "6x realtime"),
        "p50_frame_latency_ms": 447.15,
        "device": "TPU v5 lite",
        "peak_tflops_assumed": 197.0,
        "smoke": False,
        "configs": configs,
    }


def test_headline_line_fits_capture_window_and_parses():
    line = bench.compact_headline(_fat_detail())
    assert len(line) <= bench.HEADLINE_LINE_CAP
    parsed = json.loads(line)
    assert parsed["metric"] == "multimodal_pipeline_frames_per_sec"
    assert parsed["value"] == 6.94
    assert parsed["vs_baseline"] == 92.55
    # the per-config summary survives at this size
    assert parsed["summary"]["headline_mfu"] == 0.0964
    assert parsed["summary"]["serving_speedup"] == 0.37


def test_headline_line_cap_is_inside_driver_tail_window():
    # the driver keeps ~2000 chars; the cap must leave room for the
    # newline plus part of the preceding detail line being present
    assert bench.HEADLINE_LINE_CAP <= 1500


def test_headline_drops_fields_rather_than_overflow():
    detail = _fat_detail()
    detail["unit"] = "x" * 2000  # pathological prose field
    line = bench.compact_headline(detail)
    assert len(line) <= bench.HEADLINE_LINE_CAP
    parsed = json.loads(line)
    # the essentials can never be dropped
    assert parsed["metric"] and parsed["vs_baseline"] == 92.55


def test_subset_runs_do_not_clobber_detail_file(tmp_path, monkeypatch):
    """A subset bench run must never overwrite BENCH_DETAIL.json -- the
    repo's committed end-to-end evidence record (round-5 review
    finding: an llm-only run replaced the full record with a partial
    one whose headline masqueraded as the pipeline metric)."""
    import subprocess

    repo = Path(__file__).resolve().parent.parent
    detail = repo / "BENCH_DETAIL.json"
    before = detail.read_text() if detail.exists() else None
    import os
    env = dict(os.environ)
    env.update(AIKO_BENCH_SMOKE="1", AIKO_BENCH_PLATFORM="cpu",
               AIKO_BENCH_CONFIGS="text", JAX_PLATFORMS="cpu")
    result = subprocess.run(
        [sys.executable, str(repo / "bench.py")], env=env,
        capture_output=True, text=True, timeout=600)
    assert result.returncode == 0, result.stderr[-2000:]
    final = result.stdout.strip().splitlines()[-1]
    parsed = json.loads(final)
    # honest labeling: a subset headline names its config
    assert parsed["metric"] == "text_headline_subset_run"
    after = detail.read_text() if detail.exists() else None
    assert after == before, "subset run clobbered BENCH_DETAIL.json"


def test_device_path_without_a_tpu_exits_nonzero_and_prints_no_metric():
    """No CPU fallback: without AIKO_BENCH_PLATFORM=cpu the bench is on
    the device path, and a run that finds no TPU must stop before any
    configuration -- never a CPU number under a device metric's name
    (the retired fallback branch recorded exactly that once)."""
    import os
    import subprocess

    repo = Path(__file__).resolve().parent.parent
    env = dict(os.environ)
    env.pop("AIKO_BENCH_PLATFORM", None)
    env.update(AIKO_BENCH_SMOKE="1", AIKO_BENCH_CONFIGS="text",
               JAX_PLATFORMS="cpu")
    result = subprocess.run(
        [sys.executable, str(repo / "bench.py")], env=env,
        capture_output=True, text=True, timeout=300)
    assert result.returncode != 0
    assert result.stdout.strip() == ""
    assert "AIKO_BENCH_PLATFORM=cpu" in result.stderr
