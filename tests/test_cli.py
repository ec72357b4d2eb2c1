"""CLI tests. The heavyweight commands (run-job, serve) are driven
in their own layers' tests; here the parser contract, simulate, train, and
health-check paths are exercised in-process."""

import json

import pytest

from realtime_fraud_detection_tpu.cli import _auc, build_parser, main


class TestParser:
    def test_requires_command(self):
        with pytest.raises(SystemExit):
            build_parser().parse_args([])

    @pytest.mark.parametrize("argv", [
        ["simulate", "--count", "10"],
        ["run-job", "--count", "100", "--analytics"],
        ["serve", "--port", "9999"],
        ["train", "--rows", "500"],
        ["lint", "--format", "json"],
        ["lint", "--lockwatch", "--fast"],
        ["health-check", "--url", "http://x"],
        ["topics"],
    ])
    def test_all_subcommands_parse(self, argv):
        args = build_parser().parse_args(argv)
        assert callable(args.fn)

    @pytest.mark.parametrize("argv", [
        ["run-job", "--mega"],
        ["serve", "--mega"],
        ["kernel-drill", "--fast", "--mega"],
        ["bench"],
    ])
    def test_what_was_deleted_is_a_usage_error(self, argv, capsys):
        """The megakernel's switch and the pre-benchmark ``bench`` command
        are gone (PR 28): asking for one is argparse's error, not a
        silently ignored flag."""
        with pytest.raises(SystemExit) as err:
            build_parser().parse_args(argv)
        assert err.value.code == 2
        assert argv[-1].lstrip("-") in capsys.readouterr().err


class TestSimulate:
    def test_writes_jsonl(self, tmp_path, capsys):
        out = tmp_path / "txns.jsonl"
        rc = main(["simulate", "--count", "120", "--users", "50",
                   "--merchants", "20", "--output", str(out)])
        assert rc == 0
        lines = out.read_text().strip().split("\n")
        assert len(lines) == 120
        txn = json.loads(lines[0])
        assert {"transaction_id", "user_id", "merchant_id", "amount",
                "timestamp"} <= set(txn)


class TestTopics:
    def test_lists_contract(self, capsys):
        assert main(["topics"]) == 0
        out = capsys.readouterr().out
        assert "payment-transactions" in out and "partitions=12" in out


class TestTrain:
    def test_trains_and_checkpoints(self, tmp_path, capsys):
        rc = main(["train", "--rows", "2000", "--trees", "8",
                   "--users", "200", "--merchants", "50",
                   "--out", str(tmp_path / "ckpt")])
        assert rc == 0
        report = json.loads(capsys.readouterr().out.strip().split("\n")[-1])
        assert report["auc"] > 0.7          # trees learn the synthetic rule
        assert (tmp_path / "ckpt" / "step_0000000000" / "manifest.json").exists()


class TestValidate:
    def test_validate_gates_on_auc_and_writes_textfile(self, tmp_path, capsys):
        """train -> validate on a FRESH stream: the reference's
        model-validation CronJob analog (ci-cd-pipeline.yaml:351-390),
        exit code = quality gate."""
        assert main(["train", "--rows", "2500", "--trees", "10",
                     "--users", "300", "--merchants", "60",
                     "--out", str(tmp_path / "ckpt")]) == 0
        capsys.readouterr()
        prom = tmp_path / "val.prom"
        rc = main(["validate", "--checkpoint-dir", str(tmp_path / "ckpt"),
                   "--rows", "1024", "--users", "300", "--merchants", "60",
                   "--min-auc", "0.6", "--metrics-out", str(prom)])
        report = json.loads(capsys.readouterr().out.strip().split("\n")[-1])
        assert rc == 0 and report["passed"] is True
        assert report["auc"] >= 0.6 and report["n"] == 1024
        text = prom.read_text()
        assert "rtfd_validation_auc" in text
        assert "rtfd_validation_passed 1" in text

        # an unreachable bar fails the job (the CronJob's failure signal)
        rc = main(["validate", "--checkpoint-dir", str(tmp_path / "ckpt"),
                   "--rows", "512", "--users", "300", "--merchants", "60",
                   "--min-auc", "0.999"])
        report = json.loads(capsys.readouterr().out.strip().split("\n")[-1])
        assert rc == 1 and report["passed"] is False


class TestHealthCheck:
    def test_unreachable_is_unhealthy(self, capsys):
        rc = main(["health-check", "--url", "http://127.0.0.1:1",
                   "--timeout", "0.2"])
        assert rc == 1
        assert json.loads(capsys.readouterr().out)["healthy"] is False


class TestAuc:
    def test_auc_orders_correctly(self):
        import numpy as np

        y = np.array([0, 0, 1, 1], float)
        assert _auc(y, np.array([0.1, 0.2, 0.8, 0.9])) == 1.0
        assert _auc(y, np.array([0.9, 0.8, 0.2, 0.1])) == 0.0
        assert _auc(np.zeros(4), np.ones(4)) == 0.5


class TestAucTies:
    def test_tied_scores_average_ranks(self):
        import numpy as np

        # all-tied scores carry no information -> AUC must be 0.5 in both
        # label orders (ordinal ranks would give 1.0 / 0.0)
        assert _auc(np.array([0.0, 1.0]), np.array([0.5, 0.5])) == 0.5
        assert _auc(np.array([1.0, 0.0]), np.array([0.5, 0.5])) == 0.5


class TestAlertRouter:
    def test_routes_alerts_to_webhook_with_committed_offsets(self):
        """cli alert-router: the EventBridge->Lambda->SNS analog — consumes
        fraud-alerts, POSTs Alertmanager-v2 payloads to the webhook, commits
        offsets only after the receiver accepts (at-least-once)."""
        import http.server
        import json as _json
        import threading

        from realtime_fraud_detection_tpu.stream import topics as T
        from realtime_fraud_detection_tpu.stream.netbroker import (
            BrokerServer,
            NetBrokerClient,
        )

        received = []

        class Hook(http.server.BaseHTTPRequestHandler):
            def do_POST(self):
                body = self.rfile.read(int(self.headers["Content-Length"]))
                received.extend(_json.loads(body))
                self.send_response(200)
                self.end_headers()

            def log_message(self, *a):
                pass

        hook = http.server.HTTPServer(("127.0.0.1", 0), Hook)
        threading.Thread(target=hook.serve_forever, daemon=True).start()
        broker = BrokerServer(port=0).start()
        client = NetBrokerClient(port=broker.port)
        try:
            for i in range(5):
                client.produce(T.ALERTS, {
                    "alert_type": "FRAUD_DETECTED",
                    "transaction_id": f"t{i}",
                    "user_id": f"u{i}",
                    "amount": 100.0 + i,
                    "fraud_score": 0.9,
                    "risk_level": "HIGH",
                    "decision": "DECLINE" if i % 2 else "REVIEW",
                }, key=f"u{i}")
            rc = main([
                "alert-router", "--broker", f"127.0.0.1:{broker.port}",
                "--webhook",
                f"http://127.0.0.1:{hook.server_address[1]}/api/v2/alerts",
                "--once"])
            assert rc == 0
            assert len(received) == 5
            assert {r["annotations"]["transaction_id"]
                    for r in received} == {f"t{i}" for i in range(5)}
            assert all(r["labels"]["alertname"] == "FRAUD_DETECTED"
                       for r in received)
            sev = {r["annotations"]["transaction_id"]: r["labels"]["severity"]
                   for r in received}
            assert sev["t1"] == "critical" and sev["t0"] == "warning"
            # offsets committed: a re-run routes nothing new
            received.clear()
            rc = main([
                "alert-router", "--broker", f"127.0.0.1:{broker.port}",
                "--webhook",
                f"http://127.0.0.1:{hook.server_address[1]}/api/v2/alerts",
                "--once"])
            assert rc == 0 and received == []
        finally:
            client.close()
            broker.stop()
            hook.shutdown()

    def test_comma_broker_list_fails_over_dead_first_address(self):
        """--broker with a comma list builds an HaBrokerClient: a dead
        first address (the killed primary) must not stop the router."""
        import http.server
        import json as _json
        import socket
        import threading

        from realtime_fraud_detection_tpu.stream import topics as T
        from realtime_fraud_detection_tpu.stream.netbroker import (
            BrokerServer,
            NetBrokerClient,
        )

        received = []

        class Hook(http.server.BaseHTTPRequestHandler):
            def do_POST(self):
                body = self.rfile.read(int(self.headers["Content-Length"]))
                received.extend(_json.loads(body))
                self.send_response(200)
                self.end_headers()

            def log_message(self, *a):
                pass

        hook = http.server.HTTPServer(("127.0.0.1", 0), Hook)
        threading.Thread(target=hook.serve_forever, daemon=True).start()
        with socket.socket() as s:           # a port nobody listens on
            s.bind(("127.0.0.1", 0))
            dead_port = s.getsockname()[1]
        broker = BrokerServer(port=0).start()
        client = NetBrokerClient(port=broker.port)
        try:
            client.produce(T.ALERTS, {
                "alert_type": "FRAUD_DETECTED", "transaction_id": "tx",
                "user_id": "u", "amount": 9.0, "fraud_score": 0.95,
                "risk_level": "HIGH", "decision": "DECLINE"}, key="u")
            rc = main([
                "alert-router",
                "--broker", f"127.0.0.1:{dead_port},127.0.0.1:{broker.port}",
                "--webhook",
                f"http://127.0.0.1:{hook.server_address[1]}/alerts",
                "--once"])
            assert rc == 0
            assert [r["annotations"]["transaction_id"]
                    for r in received] == ["tx"]
        finally:
            client.close()
            broker.stop()
            hook.shutdown()


class TestRunJobResume:
    def test_second_run_resumes_from_checkpoint(self, tmp_path, capsys):
        """run-job --checkpoint-dir restores models/host-state/offsets and
        continues step numbering (the Flink restore-from-checkpoint
        behavior) instead of starting over."""
        ckpt_dir = str(tmp_path / "ck")
        argv = ["run-job", "--count", "600", "--users", "50",
                "--merchants", "20", "--batch", "64",
                "--checkpoint-dir", ckpt_dir]
        assert main(argv) == 0
        from realtime_fraud_detection_tpu.checkpoint import CheckpointManager

        first_steps = CheckpointManager(ckpt_dir).steps()
        assert first_steps, "first run wrote no checkpoints"
        capsys.readouterr()

        assert main(argv) == 0
        err = capsys.readouterr().err
        assert f"resumed from checkpoint step {max(first_steps)}" in err
        second_steps = CheckpointManager(ckpt_dir).steps()
        # numbering continued past the first run's last step
        assert max(second_steps) > max(first_steps)
