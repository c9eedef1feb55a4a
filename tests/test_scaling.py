"""Scaling stack tests: scalers, watcher, auto-scaler, resource
optimizer (SURVEY §2.2 scalers/watchers/auto-scaler/optimizer)."""

import json
import sys
import time

import pytest

from dlrover_tpu.common.messages import NodeResourceStats
from dlrover_tpu.common.node import Node
from dlrover_tpu.master.node_manager import LocalJobManager, ScalePlan
from dlrover_tpu.master.scaling import (
    AllreduceAutoScaler,
    ElasticJobScaler,
    LocalResourceOptimizer,
    ProcessScaler,
    ProcessWatcher,
    ResourcePlan,
)
from dlrover_tpu.master.stats import JobMetricCollector


def sleep_cmd(node):
    return [sys.executable, "-c", "import time; time.sleep(60)"]


class TestProcessScaler:
    def test_launch_and_remove(self):
        scaler = ProcessScaler(sleep_cmd)
        try:
            scaler.scale(ScalePlan(launch_nodes=[Node("worker", 0),
                                                 Node("worker", 1)]))
            assert sorted(scaler.alive_nodes()) == [0, 1]
            scaler.scale(ScalePlan(remove_nodes=[Node("worker", 0)]))
            assert scaler.alive_nodes() == [1]
        finally:
            scaler.stop()
        assert scaler.alive_nodes() == []


class TestProcessWatcher:
    def test_death_reported_to_job_manager(self):
        scaler = ProcessScaler(
            lambda n: [sys.executable, "-c", "pass"]  # exits immediately
        )
        jm = LocalJobManager(node_num=1)
        watcher = ProcessWatcher(scaler, jm, interval=0.1)
        try:
            scaler.scale(ScalePlan(launch_nodes=[Node("worker", 0)]))
            watcher._poll()  # sees it alive (or already dead)
            deadline = time.monotonic() + 5
            while time.monotonic() < deadline:
                watcher._poll()
                node = jm.get_node(0)
                if node is not None and node.status == "failed":
                    break
                time.sleep(0.05)
            assert jm.get_node(0).status == "failed"
        finally:
            watcher.stop()
            scaler.stop()


class RecordingScaler:
    def __init__(self):
        self.plans = []

    def scale(self, plan):
        self.plans.append(plan)


class TestAutoScaler:
    def test_relaunches_missing_workers(self):
        jm = LocalJobManager(node_num=3)
        jm.update_node_status(2, "failed", "oom")
        jm.get_node(2).relaunchable = False
        scaler = RecordingScaler()
        auto = AllreduceAutoScaler(jm, scaler, target_worker_num=3,
                                   interval=60)
        auto._reconcile()
        launch_plans = [p for p in scaler.plans if p.launch_nodes]
        assert launch_plans, "no relaunch plan produced"
        # A fresh id (not colliding with 0..2) is assigned.
        assert launch_plans[0].launch_nodes[0].id == 3

    def test_no_plan_when_at_target(self):
        jm = LocalJobManager(node_num=2)
        scaler = RecordingScaler()
        auto = AllreduceAutoScaler(jm, scaler, target_worker_num=2,
                                   interval=60)
        auto._reconcile()
        assert not [p for p in scaler.plans if p.launch_nodes]

    def test_resource_plan_executed(self):
        collector = JobMetricCollector()
        collector.collect_node_resource(
            NodeResourceStats(node_id=0, cpu_percent=200.0,
                              used_memory_mb=1000)
        )
        jm = LocalJobManager(node_num=1)
        scaler = RecordingScaler()
        auto = AllreduceAutoScaler(
            jm, scaler, resource_optimizer=LocalResourceOptimizer(collector),
            target_worker_num=1, interval=60,
        )
        auto._reconcile()
        res_plans = [p for p in scaler.plans if p.node_group_resources]
        assert res_plans
        group = res_plans[0].node_group_resources["worker"]
        assert group.node_resource.memory_mb == 1300  # peak * 1.3


class TestLocalResourceOptimizer:
    def test_empty_without_stats(self):
        opt = LocalResourceOptimizer(JobMetricCollector())
        assert opt.generate_plan(2).empty()

    def test_plan_from_stats(self):
        collector = JobMetricCollector()
        collector.collect_node_resource(
            NodeResourceStats(node_id=0, cpu_percent=150.0,
                              used_memory_mb=2048)
        )
        plan = LocalResourceOptimizer(collector).generate_plan(4)
        assert plan.worker_num == 4
        assert plan.worker_cpu == pytest.approx(2.25)  # 1.5 cores * 1.5
        assert plan.worker_memory_mb == int(2048 * 1.3)


class TestElasticJobScaler:
    def test_emits_crd_manifest(self):
        """The emitted body must be the vendored ScalePlan CRD schema
        (``scaleplan_types.go`` field names), not an ad-hoc dict."""

        class FakeClient:
            def __init__(self):
                self.bodies = []

            def patch(self, body):
                self.bodies.append(body)

        client = FakeClient()
        scaler = ElasticJobScaler(client, "job-x")
        from dlrover_tpu.common.node import NodeGroupResource, NodeResource

        scaler.scale(ScalePlan(
            node_group_resources={
                "worker": NodeGroupResource(
                    count=4,
                    node_resource=NodeResource(cpu=2.0, memory_mb=8192),
                )
            },
            launch_nodes=[Node("worker", 5)],
        ))
        body = client.bodies[0]
        assert body["kind"] == "ScalePlan"
        assert body["apiVersion"].endswith("v1alpha1")
        assert body["metadata"]["labels"]["elasticjob-name"] == "job-x"
        spec = body["spec"]
        assert spec["ownerJob"] == "job-x"
        rrs = spec["replicaResourceSpecs"]["worker"]
        assert rrs["replicas"] == 4
        assert rrs["resource"] == {"cpu": "2.0", "memory": "8192Mi"}
        (pod,) = spec["createPods"]
        assert pod["id"] == 5 and pod["type"] == "worker"
        assert pod["rankIndex"] == 5
        assert body["status"]["phase"] == "Pending"

    def test_manifest_round_trips(self):
        from dlrover_tpu.master.crd import ScalePlanCRD, scaleplan_from_plan

        crd = scaleplan_from_plan(
            ScalePlan(launch_nodes=[Node("worker", 1)],
                      remove_nodes=[Node("worker", 0)]),
            "job-y", seq=3,
        )
        doc = crd.to_manifest()
        back = ScalePlanCRD.from_manifest(doc)
        assert back.name == "job-y-scaleplan-3"
        assert [p.id for p in back.spec.create_pods] == [1]
        assert [p.id for p in back.spec.remove_pods] == [0]


class TestScalePlanReconciler:
    def test_round_trip_autoscaler_to_new_process(self):
        """Auto-scaler -> ScalePlan CRD ->
        reconciler -> the platform actually launches the node (the same
        watch->realize->status flow elasticjob_controller.go runs)."""
        from dlrover_tpu.master.crd import (
            PHASE_SUCCEEDED,
            ScalePlanReconciler,
            ScalePlanStore,
        )

        jm = LocalJobManager(node_num=2)
        jm.update_node_status(1, "failed", "killed")
        jm.get_node(1).relaunchable = False

        store = ScalePlanStore()
        process_scaler = ProcessScaler(sleep_cmd)
        reconciler = ScalePlanReconciler(store, process_scaler)
        auto = AllreduceAutoScaler(
            jm, ElasticJobScaler(store, "job-rt"),
            target_worker_num=2, interval=60,
        )
        try:
            auto._reconcile()          # emits the CRD into the store
            reconciler.start()
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline and not store.applied:
                time.sleep(0.05)
            assert store.applied, "reconciler never applied the plan"
            applied = store.applied[0]
            assert applied.status.phase == PHASE_SUCCEEDED
            assert applied.status.finish_time is not None
            # the platform really launched the replacement node
            deadline = time.monotonic() + 5
            while time.monotonic() < deadline:
                if process_scaler.alive_nodes():
                    break
                time.sleep(0.05)
            assert process_scaler.alive_nodes()
        finally:
            reconciler.stop()
            process_scaler.stop()

    def test_remove_flows_through(self):
        from dlrover_tpu.master.crd import (
            ScalePlanReconciler,
            ScalePlanStore,
        )

        store = ScalePlanStore()
        process_scaler = ProcessScaler(sleep_cmd)
        reconciler = ScalePlanReconciler(store, process_scaler)
        ej = ElasticJobScaler(store, "job-rm")
        try:
            process_scaler.scale(
                ScalePlan(launch_nodes=[Node("worker", 7)])
            )
            assert process_scaler.alive_nodes() == [7]
            ej.scale(ScalePlan(remove_nodes=[Node("worker", 7)]))
            reconciler.start()
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline and not store.applied:
                time.sleep(0.05)
            assert store.applied
            assert process_scaler.alive_nodes() == []
        finally:
            reconciler.stop()
            process_scaler.stop()


class TestK8sClientContract:
    """The REST client must emit exactly the apiserver's custom-resource
    protocol (paths/verbs/bodies) — pinned here so a real cluster is a
    transport swap (parity: reference k8sClient/pod_scaler surface)."""

    def make(self):
        calls = []

        def transport(method, path, body):
            calls.append((method, path, body))
            if method == "GET" and path.endswith("scaleplans"):
                return 200, {"items": []}
            if method == "GET":
                from dlrover_tpu.master.crd import scaleplan_from_plan

                return 200, scaleplan_from_plan(
                    ScalePlan(), "job-k", 1
                ).to_manifest()
            return 201, {"ok": True}

        from dlrover_tpu.master.k8s import K8sElasticJobClient

        return K8sElasticJobClient(transport, namespace="ml"), calls

    def test_create_scaleplan_request_shape(self):
        from dlrover_tpu.master.crd import scaleplan_from_plan

        client, calls = self.make()
        crd = scaleplan_from_plan(
            ScalePlan(launch_nodes=[Node("worker", 2)]), "job-k", 7
        )
        client.create_scaleplan(crd)
        method, path, body = calls[0]
        assert method == "POST"
        assert path == (
            "/apis/elastic.iml.github.io/v1alpha1/namespaces/ml/"
            "scaleplans"
        )
        assert body["kind"] == "ScalePlan"
        assert body["metadata"]["name"] == "job-k-scaleplan-7"
        assert body["spec"]["createPods"][0]["id"] == 2

    def test_status_patch_subresource(self):
        client, calls = self.make()
        client.update_scaleplan_status("job-k-scaleplan-7", "Succeeded")
        method, path, body = calls[0]
        assert method == "PATCH"
        assert path.endswith("/scaleplans/job-k-scaleplan-7/status")
        assert body["status"]["phase"] == "Succeeded"

    def test_elasticjob_replica_patch(self):
        client, calls = self.make()
        client.patch_elasticjob_replicas("job-k", {"worker": 5})
        method, path, body = calls[0]
        assert method == "PATCH"
        assert path.endswith("/elasticjobs/job-k")
        assert body["spec"]["replicaSpecs"]["worker"]["replicas"] == 5

    def test_elasticjob_scaler_through_k8s_submitter(self):
        """ElasticJobScaler -> K8sScalePlanSubmitter -> apiserver create:
        the cluster path uses the same CRD emission as the local one."""
        from dlrover_tpu.master.k8s import K8sScalePlanSubmitter

        client, calls = self.make()
        scaler = ElasticJobScaler(
            K8sScalePlanSubmitter(client), "job-k"
        )
        scaler.scale(ScalePlan(launch_nodes=[Node("worker", 0)]))
        method, path, body = calls[0]
        assert method == "POST"
        assert path.endswith("/scaleplans")
        assert body["spec"]["ownerJob"] == "job-k"

    def test_error_status_raises(self):
        from dlrover_tpu.master.crd import scaleplan_from_plan
        from dlrover_tpu.master.k8s import K8sElasticJobClient

        client = K8sElasticJobClient(
            lambda m, p, b: (409, {"reason": "AlreadyExists"}),
            namespace="ml",
        )
        with pytest.raises(RuntimeError, match="409"):
            client.create_scaleplan(
                scaleplan_from_plan(ScalePlan(), "j", 1)
            )


class TestDefaultTransportLiveHTTP:
    """Exercise ``default_transport`` (the urllib path a real cluster
    uses) against a live in-test HTTP server: verbs, paths, auth header,
    and the CRD PATCH content-type (merge-patch, not application/json —
    a real apiserver 415s the latter on custom resources)."""

    @pytest.fixture()
    def server(self):
        import http.server
        import threading

        seen = []

        class Handler(http.server.BaseHTTPRequestHandler):
            def _respond(self):
                length = int(self.headers.get("Content-Length") or 0)
                body = self.rfile.read(length) if length else b""
                seen.append({
                    "method": self.command,
                    "path": self.path,
                    "content_type": self.headers.get("Content-Type"),
                    "auth": self.headers.get("Authorization"),
                    "body": json.loads(body) if body else None,
                })
                if "conflict" in self.path:
                    payload = json.dumps(
                        {"reason": "AlreadyExists", "code": 409}
                    ).encode()
                    self.send_response(409)
                    self.send_header(
                        "Content-Type", "application/json"
                    )
                    self.send_header(
                        "Content-Length", str(len(payload))
                    )
                    self.end_headers()
                    self.wfile.write(payload)
                    return
                payload = json.dumps({"ok": True, "items": []}).encode()
                self.send_response(200)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(payload)))
                self.end_headers()
                self.wfile.write(payload)

            do_GET = do_POST = do_PATCH = _respond

            def log_message(self, *a):
                pass

        httpd = http.server.ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        t = threading.Thread(target=httpd.serve_forever, daemon=True)
        t.start()
        try:
            yield f"http://127.0.0.1:{httpd.server_address[1]}", seen
        finally:
            httpd.shutdown()
            httpd.server_close()

    def test_post_and_patch_over_live_server(self, server):
        from dlrover_tpu.master.crd import scaleplan_from_plan
        from dlrover_tpu.master.k8s import (
            K8sElasticJobClient,
            default_transport,
        )

        url, seen = server
        client = K8sElasticJobClient(
            default_transport(url, token="sekrit"), namespace="ml"
        )
        client.create_scaleplan(
            scaleplan_from_plan(
                ScalePlan(launch_nodes=[Node("worker", 1)]), "job-h", 3
            )
        )
        client.update_scaleplan_status("job-h-scaleplan-3", "Succeeded")
        client.patch_elasticjob_replicas("job-h", {"worker": 2})
        client.list_scaleplans()

        post, patch_status, patch_job, listed = seen
        assert post["method"] == "POST"
        assert post["content_type"] == "application/json"
        assert post["auth"] == "Bearer sekrit"
        assert post["body"]["kind"] == "ScalePlan"
        assert patch_status["method"] == "PATCH"
        assert patch_status["content_type"] == "application/merge-patch+json"
        assert patch_status["path"].endswith("/status")
        assert patch_job["content_type"] == "application/merge-patch+json"
        assert patch_job["body"]["spec"]["replicaSpecs"]["worker"][
            "replicas"] == 2
        assert listed["method"] == "GET"

    def test_non_2xx_surfaces_as_status_not_exception(self, server):
        """urlopen raises HTTPError on >=300; the transport must turn
        that back into (status, parsed apiserver Status body) so the
        client's error branches actually fire."""
        from dlrover_tpu.master.k8s import (
            K8sElasticJobClient,
            default_transport,
        )

        url, seen = server
        client = K8sElasticJobClient(default_transport(url))
        with pytest.raises(RuntimeError, match="409"):
            client.update_scaleplan_status("conflict-plan", "Succeeded")


class TestActorScaler:
    """Ray backend contract (parity: scaler/ray_scaler.py ActorScaler):
    actor naming, create/remove protocol, alive diffing."""

    class FakeRay:
        def __init__(self):
            self.actors = {}
            self.calls = []

        def create_actor(self, name, spec):
            self.calls.append(("create", name, spec))
            self.actors[name] = spec

        def remove_actor(self, name):
            self.calls.append(("remove", name))
            self.actors.pop(name, None)

        def list_actors(self):
            return list(self.actors)

    def test_scale_creates_and_removes_actors(self):
        from dlrover_tpu.common.node import NodeResource
        from dlrover_tpu.master.ray_scaler import ActorScaler

        ray = self.FakeRay()
        scaler = ActorScaler(ray, "job-r")
        n = Node("worker", 3)
        n.resource = NodeResource(cpu=2.0, memory_mb=4096)
        scaler.scale(ScalePlan(launch_nodes=[n]))
        assert "job-r-worker-3" in ray.actors
        spec = ray.actors["job-r-worker-3"]
        assert spec["num_cpus"] == 2.0
        assert spec["memory"] == 4096 << 20
        scaler.scale(ScalePlan(remove_nodes=[Node("worker", 3)]))
        assert ray.actors == {}

    def test_alive_nodes_ignores_foreign_actors(self):
        from dlrover_tpu.master.ray_scaler import ActorScaler

        ray = self.FakeRay()
        ray.actors = {
            "job-r-worker-0": {},
            "job-r-worker-2": {},
            "other-job-worker-5": {},
            "unrelated": {},
        }
        scaler = ActorScaler(ray, "job-r")
        assert sorted(scaler.alive_nodes()) == [
            ("worker", 0), ("worker", 2)
        ]

    def test_actor_name_round_trip(self):
        from dlrover_tpu.master.ray_scaler import (
            actor_name,
            parse_actor_name,
        )

        name = actor_name("j", Node("worker", 7))
        assert parse_actor_name(name) == ("worker", 7)
        assert parse_actor_name("garbage") is None


class TestClusterWatcher:
    def test_vanished_node_reported_once_and_rearms(self):
        from dlrover_tpu.master.ray_scaler import ClusterWatcher

        jm = LocalJobManager(node_num=2)
        failures = []
        jm.add_event_callback(
            lambda event: failures.append(
                (event.node.id, event.node.status)
            ) if event.node.status == "failed" else None
        )
        alive = {0, 1}
        watcher = ClusterWatcher(lambda: alive, jm, interval=60)
        watcher._poll()
        assert failures == []
        alive.discard(1)                # platform lost node 1
        watcher._poll()
        watcher._poll()                 # no duplicate report while down
        assert [f for f in failures if f[0] == 1] == [(1, "failed")]
        # relaunch: node 1 alive again, then vanishes again -> re-report
        jm.get_node(1).update_status("running")
        alive.add(1)
        watcher._poll()
        alive.discard(1)
        jm.get_node(1).update_status("running")
        watcher._poll()
        assert [f for f in failures if f[0] == 1] == [
            (1, "failed"), (1, "failed")
        ]


class TestK8sListWatch:
    """List+watch parity (k8s_watcher.py:151) against a LIVE chunked
    HTTP server: initial list seeds pending plans, watch events stream,
    EOF reconnects from the last resourceVersion, 410 re-lists, and the
    unchanged ScalePlanReconciler realizes plans + pushes status."""

    @pytest.fixture()
    def apiserver(self):
        import http.server
        import threading

        from dlrover_tpu.master.crd import scaleplan_from_plan

        def plan_doc(seq, rv, phase=""):
            crd = scaleplan_from_plan(
                ScalePlan(launch_nodes=[Node("worker", seq)]),
                "job-w", seq,
            )
            doc = crd.to_manifest()
            doc["metadata"]["resourceVersion"] = str(rv)
            doc["status"]["phase"] = phase
            return doc

        state = {
            "watch_calls": [], "status_patches": [],
            "expire_first_watch": False,
        }

        class Handler(http.server.BaseHTTPRequestHandler):
            def do_GET(self):
                if "watch=1" in self.path:
                    state["watch_calls"].append(self.path)
                    self.send_response(200)
                    self.send_header(
                        "Content-Type", "application/json"
                    )
                    self.end_headers()
                    if (state["expire_first_watch"]
                            and len(state["watch_calls"]) == 1):
                        self.wfile.write((json.dumps({
                            "type": "ERROR",
                            "object": {"code": 410,
                                       "reason": "Expired"},
                        }) + "\n").encode())
                        return
                    n = len(state["watch_calls"])
                    # two events per connection, then EOF
                    for i in range(2):
                        seq = 10 * n + i
                        self.wfile.write((json.dumps({
                            "type": "ADDED",
                            "object": plan_doc(seq, 100 * n + i),
                        }) + "\n").encode())
                        self.wfile.flush()
                    return
                body = json.dumps({
                    "metadata": {"resourceVersion": "5"},
                    "items": [plan_doc(1, 4),
                              plan_doc(2, 5, phase="Succeeded")],
                }).encode()
                self.send_response(200)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_PATCH(self):
                length = int(self.headers.get("Content-Length") or 0)
                state["status_patches"].append(
                    (self.path,
                     json.loads(self.rfile.read(length)))
                )
                body = b"{}"
                self.send_response(200)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *a):
                pass

        httpd = http.server.ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        threading.Thread(target=httpd.serve_forever, daemon=True).start()
        try:
            yield f"http://127.0.0.1:{httpd.server_address[1]}", state
        finally:
            httpd.shutdown()
            httpd.server_close()

    def make_client(self, url):
        from dlrover_tpu.master.k8s import (
            K8sElasticJobClient,
            default_stream_transport,
            default_transport,
        )

        return K8sElasticJobClient(
            default_transport(url),
            stream_transport=default_stream_transport(url, timeout=10),
        )

    def test_watch_streams_events(self, apiserver):
        url, _ = apiserver
        client = self.make_client(url)
        events = list(client.watch_scaleplans("5"))
        assert [e[0] for e in events] == ["ADDED", "ADDED"]
        assert events[0][1].spec.create_pods[0].id == 10

    def test_source_lists_then_watches_and_reconciler_realizes(
        self, apiserver
    ):
        from dlrover_tpu.master.crd import ScalePlanReconciler
        from dlrover_tpu.master.k8s import K8sScalePlanSource

        url, state = apiserver
        source = K8sScalePlanSource(self.make_client(url),
                                    reconnect_delay=0.05)
        realized = []

        class FakeScaler:
            def scale(self, plan):
                realized.append(
                    [n.id for n in plan.launch_nodes]
                )

        rec = ScalePlanReconciler(source, FakeScaler())
        source.start()
        rec.start()
        deadline = time.time() + 20
        # list seeds plan 1 (plan 2 already Succeeded -> skipped);
        # watch connections deliver 10, 11, then reconnect 20, 21...
        while time.time() < deadline and len(realized) < 3:
            time.sleep(0.05)
        rec.stop()
        source.stop()
        flat = [i for ids in realized for i in ids]
        assert 1 in flat           # from the initial list
        assert 10 in flat and 11 in flat  # from the first watch
        assert 2 not in flat       # already-realized plan skipped
        assert len(state["watch_calls"]) >= 2  # reconnected after EOF
        # resumed from the last seen resourceVersion
        assert "resourceVersion=101" in state["watch_calls"][1]
        # reconciler pushed phases back to the status subresource
        assert any(
            "/status" in path and body["status"]["phase"] == "Succeeded"
            for path, body in state["status_patches"]
        )

    def test_410_triggers_relist(self, apiserver):
        from dlrover_tpu.master.k8s import K8sScalePlanSource

        url, state = apiserver
        state["expire_first_watch"] = True
        source = K8sScalePlanSource(self.make_client(url),
                                    reconnect_delay=0.05)
        source.start()
        got = []
        deadline = time.time() + 20
        while time.time() < deadline and len(got) < 2:
            plan = source.watch(timeout=0.2)
            if plan is not None:
                got.append(plan)
        source.stop()
        # survived the 410: re-listed (plan 1 seen twice is fine) and
        # went on to receive watch events
        assert len(state["watch_calls"]) >= 2
        assert got


class TestWatchSourceScoping:
    def test_plans_queue_exactly_once(self):
        """A still-Pending plan arriving from list AND watch (or a 410
        re-list) must realize once, not twice."""
        from dlrover_tpu.master.crd import scaleplan_from_plan
        from dlrover_tpu.master.k8s import (
            K8sElasticJobClient,
            K8sScalePlanSource,
        )

        crd = scaleplan_from_plan(
            ScalePlan(launch_nodes=[Node("worker", 1)]), "job-d", 1
        )
        src = K8sScalePlanSource(
            K8sElasticJobClient(lambda m, p, b: (200, {}))
        )
        src._offer(crd)
        src._offer(crd)  # watch duplicate
        assert src.watch(timeout=0.1) is not None
        assert src.watch(timeout=0.1) is None

    def test_selector_scopes_to_job(self):
        """Two masters in one namespace: the source only lists/watches
        its own job's plans (elasticjob-name label selector)."""
        from dlrover_tpu.master.k8s import (
            K8sElasticJobClient,
            K8sScalePlanSource,
        )

        paths = []

        def transport(method, path, body):
            paths.append(path)
            return 200, {"metadata": {"resourceVersion": "1"},
                         "items": []}

        def stream(path):
            paths.append(path)
            return iter(())  # immediate EOF

        client = K8sElasticJobClient(
            transport, stream_transport=stream
        )
        source = K8sScalePlanSource(client, job_name="job-a",
                                    reconnect_delay=0.01)
        source.start()
        deadline = time.time() + 5
        while time.time() < deadline and len(paths) < 3:
            time.sleep(0.02)
        source.stop()
        assert any("labelSelector=elasticjob-name%3Djob-a" in p
                   or "labelSelector=elasticjob-name=job-a" in p
                   for p in paths if "watch" not in p)
        assert any("labelSelector" in p for p in paths
                   if "watch=1" in p)
