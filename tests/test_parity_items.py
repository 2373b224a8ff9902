# Small parity items (VERDICT round-1 missing #7/#8 + media gaps):
# config bootstrap (TCP probe + UDP MCU responder), AOP tracing proxy,
# contention-diagnosing lock, audio FFT/resampler elements, and the
# video<->images converter pipelines.

import json
import os
import socket
import threading
import time

import numpy as np
import pytest

from aiko_services_tpu.utils import (
    BootstrapResponder, DiagnosticLock, get_mqtt_host, probe_tcp)


class TestConfigBootstrap:
    def test_probe_tcp_detects_listener(self):
        listener = socket.socket()
        listener.bind(("127.0.0.1", 0))
        listener.listen(1)
        port = listener.getsockname()[1]
        try:
            assert probe_tcp("127.0.0.1", port, timeout=1.0)
        finally:
            listener.close()
        assert not probe_tcp("127.0.0.1", port, timeout=0.2)

    def test_get_mqtt_host_picks_first_reachable(self):
        listener = socket.socket()
        listener.bind(("127.0.0.1", 0))
        listener.listen(1)
        port = listener.getsockname()[1]
        # a dead candidate: a localhost port nothing listens on, reached
        # via a hostname alias so the candidate strings differ
        with socket.socket() as probe:
            probe.bind(("127.0.0.1", 0))
            dead_port = probe.getsockname()[1]
        try:
            host = get_mqtt_host(candidates=["127.0.0.1"], port=port,
                                 timeout=0.2)
            assert host == "127.0.0.1"
        finally:
            listener.close()
        assert get_mqtt_host(candidates=["127.0.0.1"], port=dead_port,
                             timeout=0.2) is None

    def test_bootstrap_responder_replies_with_endpoint(self, monkeypatch):
        monkeypatch.setenv("AIKO_NAMESPACE", "aiko_test")
        responder = BootstrapResponder(port=0, mqtt_host="broker.local",
                                       mqtt_port=1884)
        try:
            client = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
            client.settimeout(5.0)
            client.sendto(b"boot?", ("127.0.0.1", responder.port))
            reply, _ = client.recvfrom(512)
            assert reply == b"(boot aiko_test broker.local 1884)"
            client.close()
        finally:
            responder.close()


class TestTracingProxy:
    def test_traces_enter_exit_with_result(self):
        from aiko_services_tpu.runtime import trace_all_methods

        class Thing:
            value = 41

            def bump(self, by):
                return self.value + by

        events = []

        def tracer(name, phase, elapsed, args, result):
            events.append((name, phase, result))

        proxy = trace_all_methods(Thing(), tracer)
        assert proxy.bump(1) == 42
        assert proxy.value == 41          # non-callables pass through
        assert events == [("bump", "enter", None), ("bump", "exit", 42)]

    def test_traces_exceptions(self):
        from aiko_services_tpu.runtime import trace_all_methods

        class Boom:
            def go(self):
                raise RuntimeError("nope")

        events = []
        proxy = trace_all_methods(
            Boom(), lambda name, phase, elapsed, args, result:
            events.append(phase))
        with pytest.raises(RuntimeError):
            proxy.go()
        assert events == ["enter", "error"]

    def test_default_tracer_logs(self):
        import logging
        from aiko_services_tpu.runtime import trace_all_methods
        from aiko_services_tpu.runtime import proxy as proxy_module

        class Thing:
            def ping(self):
                return "pong"

        records = []
        handler = logging.Handler()
        handler.emit = lambda record: records.append(record.getMessage())
        proxy_module._LOGGER.addHandler(handler)
        try:
            trace_all_methods(Thing()).ping()
        finally:
            proxy_module._LOGGER.removeHandler(handler)
        joined = " ".join(records)
        assert "TRACE" in joined and "ping" in joined


class TestDiagnosticLock:
    def test_uncontended_fast_path(self):
        lock = DiagnosticLock("fast")
        with lock:
            assert lock.locked()
        assert not lock.locked()
        assert lock.contentions == 0

    def test_contention_is_counted_and_logged(self):
        import logging
        from aiko_services_tpu.utils import lock as lock_module
        records = []
        handler = logging.Handler()
        handler.emit = lambda record: records.append(record.getMessage())
        lock_module._LOGGER.addHandler(handler)
        lock = DiagnosticLock("busy", warn_seconds=0.05)
        lock.acquire()
        done = threading.Event()

        def waiter():
            lock.acquire()
            lock.release()
            done.set()

        thread = threading.Thread(target=waiter)
        thread.start()
        time.sleep(0.2)  # several warn_seconds slices elapse
        lock.release()
        assert done.wait(5)
        thread.join(5)
        lock_module._LOGGER.removeHandler(handler)
        assert lock.contentions == 1
        contended = [r for r in records if "busy" in r and "contended" in r]
        assert len(contended) >= 2  # re-warns each warn_seconds slice
        assert "held by MainThread" in contended[0]

    def test_acquire_timeout_expires(self):
        lock = DiagnosticLock("timed", warn_seconds=0.05)
        lock.acquire()
        assert lock.acquire(timeout=0.15) is False
        lock.release()


    def test_nonblocking_contention(self):
        lock = DiagnosticLock("nb")
        lock.acquire()
        assert lock.acquire(blocking=False) is False
        lock.release()


class TestAudioElements:
    @staticmethod
    def _element(cls, params=None):
        params = params or {}
        element = cls.__new__(cls)
        element.get_parameter = (
            lambda name, default=None, stream=None:
            params.get(name, default))
        return element

    def test_fft_finds_tone_frequency(self):
        from aiko_services_tpu.elements import AudioFFT
        from aiko_services_tpu.elements.audio_io import synthesize_tone
        element = self._element(AudioFFT)
        audio = synthesize_tone(440.0, 0.5)
        _, outputs = AudioFFT.process_frame(element, None, audio)
        spectrum = np.asarray(outputs["spectrum"])
        frequencies = np.asarray(outputs["frequencies"])
        peak_hz = frequencies[int(np.argmax(spectrum))]
        assert abs(peak_hz - 440.0) < 4.0

    def test_resample_halves_and_preserves_tone(self):
        from aiko_services_tpu.elements import AudioResample
        from aiko_services_tpu.elements.audio_io import synthesize_tone
        element = self._element(AudioResample, {"rate_in": 16000,
                                                "rate_out": 8000})
        audio = synthesize_tone(440.0, 0.25)
        _, outputs = AudioResample.process_frame(element, None, audio)
        resampled = np.asarray(outputs["audio"])
        assert outputs["sample_rate"] == 8000
        assert abs(len(resampled) - len(audio) // 2) <= 1
        spectrum = np.abs(np.fft.rfft(resampled))
        peak_hz = np.fft.rfftfreq(len(resampled), 1 / 8000)[
            int(np.argmax(spectrum))]
        assert abs(peak_hz - 440.0) < 8.0

    def test_resample_preserves_batch_shape(self):
        from aiko_services_tpu.elements import AudioResample
        element = self._element(
            AudioResample, {"rate_in": 16000, "rate_out": 8000})
        audio = np.random.default_rng(0).standard_normal(
            (2, 1000)).astype(np.float32)
        _, outputs = AudioResample.process_frame(element, None, audio)
        assert np.asarray(outputs["audio"]).shape == (2, 500)

    def test_resample_identity(self):
        from aiko_services_tpu.elements import AudioResample
        element = self._element(AudioResample, {"rate_in": 16000,
                                                "rate_out": 16000})
        audio = np.arange(100, dtype=np.float32)
        _, outputs = AudioResample.process_frame(element, None, audio)
        np.testing.assert_array_equal(np.asarray(outputs["audio"]), audio)


class TestConverterPipelines:
    @pytest.mark.parametrize("path", [
        "examples/pipeline_video_to_images.json",
        "examples/pipeline_images_to_video.json",
    ])
    def test_definitions_parse(self, path):
        from aiko_services_tpu.pipeline import parse_pipeline_definition
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        with open(os.path.join(repo, path)) as handle:
            definition = parse_pipeline_definition(json.load(handle))
        assert definition.name in ("video_to_images", "images_to_video")

    def test_images_to_video_roundtrip(self, tmp_path):
        """Write PNGs, run the converter pipeline, read the video back:
        the reference's standalone converters as a framework graph."""
        cv2 = pytest.importorskip("cv2")
        import queue
        from PIL import Image
        from aiko_services_tpu.runtime import Process
        from aiko_services_tpu.pipeline import create_pipeline

        frames_dir = tmp_path / "frames"
        frames_dir.mkdir()
        for index in range(3):
            array = np.full((32, 32, 3), index * 60, np.uint8)
            Image.fromarray(array).save(
                frames_dir / f"frame_{index:02d}.png")
        out_path = tmp_path / "out.avi"
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        with open(os.path.join(
                repo, "examples/pipeline_images_to_video.json")) as handle:
            definition = json.load(handle)
        definition["elements"][0]["parameters"]["data_sources"] = [
            str(frames_dir / "*.png")]
        definition["elements"][1]["parameters"].update(
            {"data_targets": [str(out_path)], "frame_rate": 5,
             "fourcc": "MJPG"})
        process = Process(transport_kind="loopback")
        pipeline = create_pipeline(process, definition)
        process.run(in_thread=True)
        responses = queue.Queue()
        pipeline.create_stream("s1", queue_response=responses)
        for _ in range(3):
            responses.get(timeout=20)
        deadline = time.monotonic() + 10
        while "s1" in pipeline.streams and time.monotonic() < deadline:
            time.sleep(0.05)  # generator exhaustion closes the writer
        process.terminate()
        capture = cv2.VideoCapture(str(out_path))
        count = 0
        while capture.read()[0]:
            count += 1
        capture.release()
        assert count == 3


class TestMicrophoneSpeaker:
    """The reference's mic/speaker seats (audio_io.py:440-640) with the
    mute protocol, exercised over a fake sounddevice module."""

    @staticmethod
    def _fake_sounddevice(recorded, played):
        import types
        fake = types.ModuleType("sounddevice")

        def rec(samples, samplerate, channels, dtype):
            recorded.append(samples)
            return np.full((samples, 1), 0.25, np.float32)

        fake.rec = rec
        fake.play = lambda array, samplerate: played.append(
            (np.asarray(array), samplerate))
        fake.wait = lambda: None
        return fake

    def test_gated_without_sounddevice(self, monkeypatch):
        import sys
        from aiko_services_tpu.elements import MicrophoneSource
        # force ImportError even on hosts that have sounddevice
        monkeypatch.setitem(sys.modules, "sounddevice", None)
        element = MicrophoneSource.__new__(MicrophoneSource)
        element.share = {}
        element.get_parameter = (
            lambda name, default=None, stream=None: default)
        event, outputs = MicrophoneSource.start_stream(element, None, "s")
        from aiko_services_tpu.pipeline import StreamEvent
        assert event == StreamEvent.ERROR
        assert "sounddevice" in outputs["diagnostic"]

    def test_speaker_mutes_discovered_microphone(self, monkeypatch):
        import sys
        import queue as queue_module
        from aiko_services_tpu.runtime import Process, Registrar
        from aiko_services_tpu.pipeline import create_pipeline
        from aiko_services_tpu.transport.loopback import get_broker
        from aiko_services_tpu.elements.robot import RobotActor  # any svc

        recorded, played = [], []
        monkeypatch.setitem(
            sys.modules, "sounddevice",
            self._fake_sounddevice(recorded, played))

        process = Process(transport_kind="loopback")
        Registrar(process, search_timeout=0.05)
        # stand-in microphone service: capture (update mute ...) on its
        # control topic (the ECProducer normally consumes these)
        mic = RobotActor(process, name="mic_service")
        mutes = []
        process.add_message_handler(
            lambda topic, payload: mutes.append(str(payload)),
            f"{mic.topic_path}/control")
        definition = {
            "name": "playback",
            "graph": ["(tone (speaker))"],
            "elements": [
                {"name": "tone", "output": [{"name": "audio"}],
                 "parameters": {"data_sources": [[440, 0.01]]},
                 "deploy": {"local": {
                     "module": "aiko_services_tpu.elements",
                     "class_name": "ToneSource"}}},
                {"name": "speaker", "input": [{"name": "audio"}],
                 "output": [{"name": "audio"}],
                 "parameters": {"microphone_service": "mic_service"},
                 "deploy": {"local": {
                     "module": "aiko_services_tpu.elements",
                     "class_name": "SpeakerSink"}}},
            ],
        }
        pipeline = create_pipeline(process, definition)
        process.run(in_thread=True)
        # warm registrar discovery so the speaker finds the microphone
        from aiko_services_tpu.runtime import ServiceFilter
        from aiko_services_tpu.runtime.share import (
            services_cache_create_singleton)
        cache = services_cache_create_singleton(process)
        deadline = time.monotonic() + 5
        while (not list(cache.services.filter_services(
                ServiceFilter(name="mic_service")))
               and time.monotonic() < deadline):
            get_broker().drain()
            time.sleep(0.01)
        responses = queue_module.Queue()
        pipeline.create_stream("s1", queue_response=responses)
        responses.get(timeout=10)
        assert played and played[0][1] == 16000
        deadline = time.monotonic() + 5
        while len(mutes) < 2 and time.monotonic() < deadline:
            get_broker().drain()
            time.sleep(0.01)
        assert any("mute" in m and "true" in m for m in mutes), mutes
        assert any("mute" in m and "false" in m for m in mutes), mutes
        process.terminate()

    def test_microphone_chunks_and_mute_zeroing(self, monkeypatch):
        import sys
        recorded, played = [], []
        monkeypatch.setitem(
            sys.modules, "sounddevice",
            self._fake_sounddevice(recorded, played))
        from aiko_services_tpu.runtime import Process
        from aiko_services_tpu.pipeline import create_pipeline
        import queue as queue_module

        definition = {
            "name": "mic_pipe",
            "graph": ["(mic)"],
            "elements": [
                {"name": "mic", "output": [{"name": "audio"}],
                 "parameters": {"chunk_seconds": 0.01, "frame_window": 1},
                 "deploy": {"local": {
                     "module": "aiko_services_tpu.elements",
                     "class_name": "MicrophoneSource"}}},
            ],
        }
        process = Process(transport_kind="loopback")
        pipeline = create_pipeline(process, definition)
        process.run(in_thread=True)
        responses = queue_module.Queue()
        pipeline.create_stream("s1", queue_response=responses)
        _, _, outputs = responses.get(timeout=10)
        audio = np.asarray(outputs["audio"])
        assert audio.shape == (160,)           # 0.01 s at 16 kHz
        assert np.allclose(audio, 0.25)        # live chunk
        # live mute: flip the share flag, next chunks are zeroed
        element = pipeline.elements["mic"]
        element.share["mute"] = "true"  # wire form: EC stores strings
        # (a chunk is 10 ms: on a loaded machine dozens are already on
        # their way when the flag flips)
        for _ in range(100):
            _, _, outputs = responses.get(timeout=10)
            if np.allclose(np.asarray(outputs["audio"]), 0.0):
                break
        assert np.allclose(np.asarray(outputs["audio"]), 0.0)
        pipeline.destroy_stream("s1")
        process.terminate()
