VERSION ?= latest
IMAGES = engine gateway operator loadtest

proto:
	protoc -I proto --python_out=seldon_core_tpu/proto_gen proto/prediction.proto proto/seldon_deployment.proto

native:
	g++ -O3 -std=c++17 -fPIC -shared -pthread -o native/libdataplane.so native/dataplane.cpp native/fastcodec.cpp
	g++ -O3 -std=c++17 -fPIC -shared -o native/libfastcodec.so native/fastcodec.cpp
	g++ -O2 -std=c++17 -o native/loadgen native/loadgen.cpp

test:
	python -m pytest tests/ -q

# deterministic fault-injection suite: combiner quorum, router fallback,
# breaker transitions, end-to-end deadlines, pause/drain (tests/test_chaos.py)
# + the mesh-kill lane (tests/test_mesh_kill.py): SIGKILL the coordinator
# gateway and one engine under live unary+SSE load — zero failed unary,
# >=99% streams complete, coordinator failover within one lease TTL
chaos:
	python -m pytest tests/ -q -m chaos

# causal-tracing demo: 3-node graph under fault injection, one traced
# request tree with retries/backoff, exported Perfetto-loadable artifact
# (trace_demo/trace.json) + critical-path summary (scripts/trace_demo.py)
trace-demo:
	python scripts/trace_demo.py --out trace_demo

# performance-observatory demo: a 3-node compiled ensemble under a batch
# mix, the GET /perf per-executable cost/MFU/roofline table dumped as an
# artifact (perf_demo/perf.json) + printed (scripts/perf_demo.py)
perf-demo:
	python scripts/perf_demo.py --out perf_demo

# prediction-quality demo: a 3-node graph served through a mid-run input
# distribution shift, the GET /quality drift/feedback/SLO table dumped as
# an artifact (quality_demo/quality.json) + printed
# (scripts/quality_demo.py)
quality-demo:
	python scripts/quality_demo.py --out quality_demo

# scale-out demo: gateway + 2 engine replicas, one deterministically slow
# (testing/faults.py FaultyEngine) — asserts the power-of-two-choices
# balancer steers away from it and that SELDON_TPU_REPLICAS=0 restores
# the single-engine path (scale_demo/scale.json artifact)
scale-demo:
	python scripts/scale_demo.py --out scale_demo

# autopilot demo: learned cost-model shed-before-dispatch — a heavy
# tight-deadline class is refused with typed 503s at admission (zero
# wasted device dispatches, measured exactly via the perf observatory's
# dispatched-row delta) and the serveable class's p99 improves; proves
# the SELDON_TPU_AUTOPILOT=0 kill switch restores the reactive path.
# Artifact autopilot_demo/autopilot.json + the GET /autopilot page
# (scripts/autopilot_demo.py; docs/operations.md "reading the
# /autopilot page")
autopilot-demo:
	python scripts/autopilot_demo.py --out autopilot_demo

# safe-rollout demo: shadow mirroring -> firehose replay vet -> staged
# canary under injected drift -> automatic rollback with zero failed
# live requests; proves both kill switches (SELDON_TPU_SHADOW=0,
# SELDON_TPU_ROLLOUTS=0).  Artifact canary_demo/rollout.json +
# shadow.json + replay.json (scripts/canary_demo.py; docs/operations.md
# "safe rollout" runbook)
canary-demo:
	python scripts/canary_demo.py --out canary_demo

# overload-survival demo: a 10x-share hog tenant vs a well-behaved
# victim over a fixed-capacity engine — fair admission holds the
# victim's p99, the brownout ladder engages and reverts in order, and
# the kill-switch arm (SELDON_TPU_BROWNOUT=0 + SELDON_TPU_TENANCY=0)
# shows the starvation this layer prevents.  Artifact
# overload_demo/overload.json (scripts/overload_demo.py;
# docs/operations.md "Surviving overload")
overload-demo:
	JAX_PLATFORMS=cpu python scripts/overload_demo.py --out overload_demo

# disaggregated-generation demo: 1 prefill + 2 decode CPU replicas,
# KV blocks streamed over the relay's OP_KVSTREAM lane — proves
# token-identity vs unified, handoffs visible in /stats + the firehose,
# the decode-direct typed 503, and the SELDON_TPU_DISAGG=0 kill switch.
# Artifact disagg_demo/disagg.json (scripts/disagg_demo.py;
# docs/operations.md "Disaggregated generation")
disagg-demo:
	JAX_PLATFORMS=cpu python scripts/disagg_demo.py --out disagg_demo

# fleet observability demo: a disaggregated generation traced END TO
# END through the gateway's federated /trace (one causal tree across
# gateway, prefill, KV-handoff and decode processes, critical path
# summing to the root), a +30ms FaultyEngine replica surfacing as the
# /fleet outlier, a coordinated profile window manifest with overlap
# refusal, and the SELDON_TPU_FLEET=0 kill-switch contrast.  Artifacts
# fleet_demo/fleet.json + trace_perfetto.json (scripts/fleet_demo.py;
# docs/operations.md "The fleet observability plane")
fleet-demo:
	JAX_PLATFORMS=cpu python scripts/fleet_demo.py --out fleet_demo

# cost-attribution demo: two tenants with skewed load through the
# micro-batcher AND the continuous-batching scheduler — the cost ledger
# (utils/costledger.py) must split each fenced device wall 3:2 with the
# pad tax following real shares, keep the accounting identity
# (accounted_fraction == 1.0), integrate KV-block-seconds, and the
# usage-weighted WFQ arm (SELDON_TPU_QOS_USAGE_WEIGHTED=1) must drain
# the cheap tenant ahead of the hog.  Artifact cost_demo/costs.json
# (scripts/cost_demo.py; docs/operations.md "Reading the /costs page")
cost-demo:
	JAX_PLATFORMS=cpu python scripts/cost_demo.py --out cost_demo

# postmortem demo: at SELDON_TPU_TRACE_SAMPLE=0.01 an injected +30 ms
# dispatch outlier must be KEPT by the tail-sampled recorder
# (utils/postmortem.py) with the explainer naming the guilty phase,
# while SELDON_TPU_POSTMORTEM=0 keeps nothing and restores the plain
# traceparent flags byte.  Artifact postmortem_demo/postmortem.json
# (scripts/postmortem_demo.py; docs/operations.md "Reading a
# postmortem")
postmortem-demo:
	JAX_PLATFORMS=cpu python scripts/postmortem_demo.py --out postmortem_demo

# perf-corpus demo: restart warm-start off the durable dispatch ledger
# (utils/perfcorpus.py) — a freshly-booted engine must price
# previously-seen shapes BEFORE its first dispatch (autopilot keys > 0
# at boot), and the SELDON_TPU_CORPUS=0 arm must boot cold.  Artifact
# corpus_demo/corpus.json + the GET /corpus page (scripts/corpus_demo.py;
# docs/operations.md "Fleet-truth burn and the perf corpus")
corpus-demo:
	JAX_PLATFORMS=cpu python scripts/corpus_demo.py --out corpus_demo

# decode flight-recorder demo: saturated genserver run that prints the
# per-tick timeline (kind, host/device split, bubbles by cause) and the
# bubble-ledger breakdown, checks host+device+bubble accounts for >=95%
# of scheduler wall, and writes the /genperf document.  Artifact
# decode_demo/genperf.json (scripts/decode_demo.py; docs/operations.md
# "Reading the /genperf page")
decode-demo:
	JAX_PLATFORMS=cpu python scripts/decode_demo.py --out decode_demo

# binary-wire demo: sequential bit-exact JSON-vs-binary parity through
# gateway->relay->engine, a coalesced burst (N requests, fewer relay
# frames), the floor/copy A/B, and the SELDON_TPU_WIRE=0 kill switch.
# Artifact wire_demo/wire.json (scripts/wire_demo.py; docs/
# external-api.md "binary tensor wire contract")
wire-demo:
	JAX_PLATFORMS=cpu python scripts/wire_demo.py --out wire_demo

# whole-graph fusion demo: fused-vs-interpreter equivalence on a served
# graph, the fusion plan off /stats, the /perf per-node phase
# decomposition, and the SELDON_TPU_GRAPH_FUSE=0 kill switch.  Artifact
# fusion_demo/fusion.json (scripts/fusion_demo.py; docs/operations.md
# "The fused graph path")
fusion-demo:
	JAX_PLATFORMS=cpu python scripts/fusion_demo.py --out fusion_demo

# guided end-to-end walkthroughs (the reference's notebooks role):
# canary shift, 8-member ensemble, epsilon-greedy feedback, SSE streaming
demos:
	python examples/demos.py all

# full model lifecycle: train -> checkpoint -> serve -> verify over REST
train-demo:
	python examples/train_then_serve.py

stack:
	python examples/local_stack.py

bundle:
	python -m seldon_core_tpu.operator.bundle

# component images (ci/docker/Dockerfile multi-stage; the reference's
# per-service Jenkinsfile build stages)
images:
	for t in $(IMAGES); do \
	  docker build -f ci/docker/Dockerfile --target $$t \
	    -t seldon-core-tpu/$$t:$(VERSION) . || exit 1 ; \
	done

publish: images
	for t in $(IMAGES); do \
	  docker push seldon-core-tpu/$$t:$(VERSION) || exit 1 ; \
	done

release-dryrun:
	@test "$(VERSION)" != "latest" || \
	  { echo "usage: make release-dryrun VERSION=X.Y.Z"; exit 2; }
	python release/release.py --version $(VERSION)

.PHONY: proto native test chaos trace-demo perf-demo quality-demo scale-demo autopilot-demo canary-demo overload-demo disagg-demo fleet-demo corpus-demo cost-demo postmortem-demo wire-demo decode-demo fusion-demo demos train-demo stack bundle images publish release-dryrun
