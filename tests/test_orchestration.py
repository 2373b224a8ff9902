# Orchestration layer tests: state machine, process manager, storage +
# request idioms, recorder, lifecycle manager/client -- hermetic over the
# loopback broker.

import os
import sys
import time

import pytest

from aiko_services_tpu.runtime import (
    LifeCycleClient, LifeCycleManager, ProcessManager, Recorder, Registrar,
    Process, StateMachine, StateMachineError, Storage, do_request)
from aiko_services_tpu.runtime.service import ServiceFilter
from aiko_services_tpu.transport import get_broker, reset_brokers
from helpers import wait_for


@pytest.fixture(autouse=True)
def clean_brokers():
    reset_brokers()
    yield
    reset_brokers()


class TestStateMachine:
    class Model:
        entered = None

        def on_enter_primary(self, **kwargs):
            self.entered = ("primary", kwargs)

    def _machine(self):
        model = self.Model()
        return model, StateMachine(
            model,
            states=["start", "primary_search", "primary", "secondary"],
            transitions=[
                {"name": "initialize", "source": "start",
                 "dest": "primary_search"},
                {"name": "promote", "source": "primary_search",
                 "dest": "primary"},
                {"name": "demote", "source": "*", "dest": "secondary"},
            ],
            initial="start")

    def test_transitions_and_callbacks(self):
        model, machine = self._machine()
        machine.transition("initialize")
        assert machine.get_state() == "primary_search"
        machine.transition("promote", reason="timeout")
        assert model.entered == ("primary", {"reason": "timeout"})

    def test_wildcard_source(self):
        _, machine = self._machine()
        machine.transition("demote")
        assert machine.get_state() == "secondary"

    def test_invalid_transition_raises(self):
        _, machine = self._machine()
        with pytest.raises(StateMachineError, match="invalid from"):
            machine.transition("promote")  # not in primary_search


class TestProcessManager:
    def test_spawn_and_reap(self):
        exits = []
        manager = ProcessManager(
            lambda process_id, code: exits.append((process_id, code)))
        child = manager.spawn(
            "sleeper", sys.executable,
            arguments=["-c", "import time; time.sleep(0.1)"],
            use_interpreter=False)
        assert "sleeper" in manager
        wait_for(lambda: ("sleeper", 0) in exits, timeout=10)
        assert child.returncode == 0
        manager.terminate()

    def test_kill(self):
        manager = ProcessManager()
        manager.spawn("stuck", sys.executable,
                      arguments=["-c", "import time; time.sleep(60)"],
                      use_interpreter=False)
        start = time.time()
        manager.kill("stuck")
        assert time.time() - start < 10
        assert "stuck" not in manager
        manager.terminate()

    def test_resolve_command_module(self):
        path = ProcessManager.resolve_command("json")
        assert path.endswith("__init__.py")


class TestSystemBootstrap:
    """`aiko system start|stop`: the one-command local deployment
    (registrar + named pipeline as detached children, pids recorded in
    a state file the stop command consumes)."""

    def _definition(self, tmp_path):
        import json
        path = tmp_path / "tiny.json"
        path.write_text(json.dumps({
            "name": "tiny", "graph": ["(source)"],
            "elements": [
                {"name": "source",
                 "output": [{"name": "text", "type": "str"}],
                 "parameters": {"data_sources": ["x"]},
                 "deploy": {"local": {
                     "module": "aiko_services_tpu.elements",
                     "class_name": "TextSource"}}}]}))
        return path

    def test_start_then_stop(self, tmp_path):
        from click.testing import CliRunner
        from aiko_services_tpu.cli import main as cli_main
        from aiko_services_tpu.cli import _pid_alive, _system_state

        state_file = tmp_path / "system.json"
        runner = CliRunner()
        result = runner.invoke(cli_main, [
            "system", "start", str(self._definition(tmp_path)),
            "--name", "boot_pipe", "--transport", "loopback",
            "--no-dashboard", "--state-file", str(state_file)])
        assert result.exit_code == 0, result.output
        state = _system_state(str(state_file))
        pids = state["pids"]
        assert set(pids) == {"registrar", "pipeline:boot_pipe"}
        assert all(_pid_alive(pid) for pid in pids.values())

        # double-start refuses while the recorded pids are alive
        again = runner.invoke(cli_main, [
            "system", "start", str(self._definition(tmp_path)),
            "--no-dashboard", "--state-file", str(state_file)])
        assert again.exit_code == 1

        status = runner.invoke(cli_main, [
            "system", "status", "--state-file", str(state_file)])
        assert status.exit_code == 0 and "up" in status.output

        result = runner.invoke(cli_main, [
            "system", "stop", "--state-file", str(state_file)])
        assert result.exit_code == 0, result.output
        wait_for(lambda: not any(_pid_alive(pid)
                                 for pid in pids.values()), timeout=15)
        assert not state_file.exists()

    def test_stop_without_state_is_an_error(self, tmp_path):
        from click.testing import CliRunner
        from aiko_services_tpu.cli import main as cli_main
        runner = CliRunner()
        result = runner.invoke(cli_main, [
            "system", "stop", "--state-file",
            str(tmp_path / "missing.json")])
        assert result.exit_code == 1

    @pytest.mark.skipif(not os.path.exists("/proc"),
                        reason="pid identity check needs /proc; without "
                               "it the fallback would SIGTERM this very "
                               "test process")
    def test_stop_refuses_recycled_pid(self, tmp_path):
        """A stale state file whose pid now belongs to an UNRELATED
        process (reboot/pid reuse) must not be signalled: this very
        test process is alive but is not an `aiko_services_tpu`
        child, so stop leaves it alone."""
        import json
        import os
        from click.testing import CliRunner
        from aiko_services_tpu.cli import main as cli_main

        state_file = tmp_path / "system.json"
        state_file.write_text(json.dumps(
            {"pids": {"registrar": os.getpid()}}))
        runner = CliRunner()
        result = runner.invoke(cli_main, [
            "system", "stop", "--state-file", str(state_file)])
        assert result.exit_code == 0, result.output
        assert "leaving it alone" in result.output
        assert not state_file.exists()


class TestStorage:
    def test_save_load_keys_delete_via_wire(self):
        process = Process(transport_kind="loopback")
        registrar_process = Process(transport_kind="loopback")
        Registrar(registrar_process, search_timeout=0.05)
        registrar_process.run(in_thread=True)
        storage = Storage(process)
        process.run(in_thread=True)

        # local API
        storage.save("alpha", {"x": 1})
        storage.save("beta", [1, 2, 3])

        results = []
        do_request(
            process, ServiceFilter(protocol="storage*"),
            lambda proxy, response_topic: proxy.keys(response_topic),
            results.append)
        wait_for(lambda: results, timeout=10)
        assert results[0] == ["alpha", "beta"]

        loaded = []
        do_request(
            process, ServiceFilter(protocol="storage*"),
            lambda proxy, response_topic: proxy.load(
                "alpha", response_topic),
            loaded.append)
        wait_for(lambda: loaded, timeout=10)
        import json
        assert json.loads(loaded[0][0]) == {"x": 1}

        storage.delete("alpha")
        gone = []
        do_request(
            process, ServiceFilter(protocol="storage*"),
            lambda proxy, response_topic: proxy.load(
                "alpha", response_topic),
            gone.append)
        wait_for(lambda: gone == [[]], timeout=10)
        process.terminate()
        registrar_process.terminate()


class TestRecorder:
    def test_log_aggregation(self):
        process = Process(transport_kind="loopback")
        recorder = Recorder(process)
        process.run(in_thread=True)
        log_topic = f"{process.namespace}/host/123/1/log"
        for index in range(5):
            process.publish(log_topic, f"line {index}")
        get_broker().drain()
        wait_for(lambda: len(recorder.records(log_topic)) == 5)
        assert recorder.topics() == [log_topic]
        assert recorder.records(log_topic)[0] == "line 0"
        process.terminate()

    def test_ring_bounded(self):
        process = Process(transport_kind="loopback")
        recorder = Recorder(process, ring_size=4)
        process.run(in_thread=True)
        log_topic = f"{process.namespace}/host/1/1/log"
        for index in range(10):
            process.publish(log_topic, f"line {index}")
        get_broker().drain()
        wait_for(lambda: recorder.records(log_topic) and
                 recorder.records(log_topic)[-1] == "line 9")
        assert recorder.records(log_topic) == [
            "line 6", "line 7", "line 8", "line 9"]
        process.terminate()


class TestLifeCycle:
    def test_handshake_and_delete(self, tmp_path):
        registrar_process = Process(transport_kind="loopback")
        Registrar(registrar_process, search_timeout=0.05)
        registrar_process.run(in_thread=True)

        manager_process = Process(transport_kind="loopback")
        changes = []
        manager = LifeCycleManager(
            manager_process, "lcm",
            client_change_handler=lambda cmd, cid: changes.append(
                (cmd, cid)))
        manager_process.run(in_thread=True)

        # the OS child is a dummy sleeper; the handshake comes from a
        # client living in this test process on the shared loopback broker
        sleeper = tmp_path / "sleeper.py"
        sleeper.write_text("import time; time.sleep(30)\n")
        client_id = manager.create_client(str(sleeper))
        record = manager.clients[client_id]
        assert record["state"] == "spawning"

        client_process = Process(transport_kind="loopback")
        client = LifeCycleClient(
            client_process, "worker", manager.topic_path, client_id)
        client.share["task"] = "indexing"
        client_process.run(in_thread=True)

        wait_for(lambda: manager.clients[client_id]["state"] == "running",
                 timeout=10)
        assert ("add", client_id) in changes

        # manager mirrors the client's share via ECConsumer
        client.ec_producer.update("task", "training")
        wait_for(lambda: manager.clients[client_id]["share"].get(
            "task") == "training", timeout=10)

        manager.delete_client(client_id)
        wait_for(lambda: client_id not in manager.clients, timeout=15)
        assert ("remove", client_id) in changes

        for process in (registrar_process, manager_process,
                        client_process):
            process.terminate()

    def test_handshake_timeout_kills_client(self, tmp_path):
        # reap path 1: handshake-lease lapse -- the OS child came up
        # but never announced; the lease kills it and drops the record
        manager_process = Process(transport_kind="loopback")
        manager = LifeCycleManager(manager_process, "lcm2",
                                   handshake_lease_time=0.2)
        manager_process.run(in_thread=True)
        sleeper = tmp_path / "sleeper.py"
        sleeper.write_text("import time; time.sleep(30)\n")
        client_id = manager.create_client(str(sleeper))
        wait_for(lambda: client_id not in manager.clients, timeout=10)
        assert client_id not in manager.process_manager
        manager_process.terminate()

    def test_client_crash_with_lwt_reaps_record_and_zombie(self,
                                                           tmp_path):
        """Reap path 2: the client's broker connection dies (severed
        transport, the fault harness's crash primitive) -- LWT
        "(absent)" fires, the registrar removes the client's services,
        and the manager's registrar watch must reap the record AND the
        wedged OS child, even though the child process never exited on
        its own."""
        registrar_process = Process(transport_kind="loopback")
        Registrar(registrar_process, search_timeout=0.05)
        registrar_process.run(in_thread=True)

        manager_process = Process(transport_kind="loopback")
        changes = []
        manager = LifeCycleManager(
            manager_process, "lcm3",
            client_change_handler=lambda cmd, cid: changes.append(
                (cmd, cid)))
        manager_process.run(in_thread=True)

        sleeper = tmp_path / "sleeper.py"
        sleeper.write_text("import time; time.sleep(30)\n")
        client_id = manager.create_client(str(sleeper))

        client_process = Process(transport_kind="loopback")
        LifeCycleClient(client_process, "worker3",
                        manager.topic_path, client_id)
        client_process.run(in_thread=True)
        wait_for(lambda: manager.clients.get(
            client_id, {}).get("state") == "running", timeout=10)
        assert client_id in manager.process_manager  # sleeper alive
        # the reap goes by the registrar's "remove" of a service the
        # manager's mirror HOLDS; the handshake reaches the manager
        # directly and can arrive before the mirror has the service
        wait_for(lambda: list(
            manager._services_cache.services.filter_services(
                ServiceFilter(name="worker3"))), timeout=10)

        client_process.transport.sever()  # crash WITH LWT
        wait_for(lambda: client_id not in manager.clients, timeout=15)
        assert ("remove", client_id) in changes
        # kill=True: the zombie OS child goes too
        wait_for(lambda: client_id not in manager.process_manager,
                 timeout=15)
        for process in (registrar_process, manager_process):
            process.terminate()

    def test_exit_handler_delivered_off_monitor_thread(self, tmp_path):
        """Reap path 3: an OS child exit is observed on the
        ProcessManager MONITOR thread, but every state mutation (record
        removal, change handler) must land on the manager's event loop
        -- the single-threaded scheduler the rest of the actor's state
        assumes."""
        import threading

        manager_process = Process(transport_kind="loopback")
        removals = []
        manager = LifeCycleManager(
            manager_process, "lcm4",
            client_change_handler=lambda cmd, cid: removals.append(
                (cmd, cid, threading.current_thread().name)))
        manager_process.run(in_thread=True)
        quick = tmp_path / "quick.py"
        quick.write_text("import sys; sys.exit(0)\n")
        client_id = manager.create_client(str(quick))
        wait_for(lambda: client_id not in manager.clients, timeout=15)
        wait_for(lambda: removals, timeout=10)
        command, removed_id, thread_name = removals[0]
        assert (command, removed_id) == ("remove", client_id)
        assert thread_name != "process-manager"   # not the monitor
        assert thread_name.endswith("-loop")      # the event loop
        manager_process.terminate()
