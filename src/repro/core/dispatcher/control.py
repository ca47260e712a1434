"""Dispatcher control plane: datasets, jobs, workers, shard hand-out.

``ControlPlaneMixin`` owns every client/worker-facing state transition that
is not snapshot materialization (``committer.py``) or fleet scheduling
(``fleet.py``).  Mutations are journaled before they are applied and
acknowledged; ``apply_control_event`` replays the same transitions from the
journal — on restart, or incrementally on a tailing hot standby.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List, Optional

from ...data.graph import Graph, Node
from ...obs.tracing import TraceContext
from ..protocol import ShardingPolicy, TaskSpec, WorkerInfo, new_id
from ..sharding import ShardManager
from ..codecs import resolve_codec
from ...snapshot.policy import Decision
from .state import _Dataset, _Job, _Worker


class ControlPlaneMixin:
    # ------------------------------------------------------------------
    # Datasets & jobs (client-facing)
    # ------------------------------------------------------------------
    def rpc_get_or_register_dataset(self, graph_bytes: bytes) -> Dict[str, Any]:
        """Register the RAW client graph; optimize once, dispatcher-side.

        The content fingerprint is taken over the bytes the client sent —
        BEFORE optimization — because optimizer passes synthesize fresh
        fused closures whose serialization is not content-stable.  Two jobs
        submitting identical pipelines must land on the same dataset_id, or
        ephemeral data sharing (§3.5) silently degrades to one cache per
        job.  Workers receive the optimized graph.
        """
        g = Graph.from_bytes(graph_bytes)
        fp = g.fingerprint()
        with self._lock:
            if fp in self._datasets_by_fp:
                return {"dataset_id": self._datasets_by_fp[fp], "fingerprint": fp}
            from ...data.optimizer import optimize_graph

            opt_bytes = optimize_graph(g).to_bytes()
            ds_id = new_id("ds")
            self._journal.append(
                "dataset_registered",
                {"dataset_id": ds_id, "graph_bytes": opt_bytes, "fingerprint": fp},
            )
            self._apply_dataset(ds_id, opt_bytes, fp)
            return {"dataset_id": ds_id, "fingerprint": fp}

    def _apply_dataset(self, ds_id: str, graph_bytes: bytes, fp: str) -> None:
        self._datasets[ds_id] = _Dataset(ds_id, graph_bytes, fp)
        self._datasets_by_fp[fp] = ds_id

    def rpc_get_or_create_job(
        self,
        dataset_id: str,
        job_name: Optional[str] = None,
        policy: str = "off",
        num_consumers: int = 0,
        sharing: bool = False,
        compression: Optional[str] = None,
        max_workers: int = 0,
        weight: float = 1.0,
        resume_offsets: bool = False,
        client_id: Optional[str] = None,
        client_codecs: Optional[List[str]] = None,
        autocache: bool = False,
        trace: Optional[Dict[str, Any]] = None,
    ) -> Dict[str, Any]:
        with self._lock:
            if job_name and job_name in self._jobs_by_name:
                job = self._jobs[self._jobs_by_name[job_name]]
                if client_id:
                    job.clients.add(client_id)
                return self._job_view(job)
            decision = None
            if autocache and self._autocache is not None:
                dataset_id, decision = self._autocache_decide(
                    dataset_id, compression=compression, client_codecs=client_codecs
                )
            payload = dict(
                job_id=new_id("job"),
                job_name=job_name or "",
                dataset_id=dataset_id,
                policy=str(ShardingPolicy.parse(policy).value),
                num_consumers=num_consumers,
                sharing=sharing,
                # codec negotiation (restricted to what the requesting
                # client can decode): the journaled payload carries the
                # RESOLVED codec so workers joining after a dispatcher
                # restart compress with the same algorithm
                compression=resolve_codec(compression, client_codecs),
                max_workers=max_workers,
                weight=max(1e-3, float(weight)),
                resume_offsets=resume_offsets,
                # journaled so a restored dispatcher partitions the source
                # into the SAME shards (ids must stay aligned with the log)
                shard_hint=max(1, len(self._workers)) * self._overpartition,
                autocache_decision=decision,
                # job-level trace root (observability): journaled so a
                # restarted/promoted dispatcher ships task specs carrying
                # the SAME trace_id the client minted
                trace=trace,
            )
            self._journal.append("job_created", payload)
            job = self._apply_job(payload)
            self._grant_initial_tasks(job)
            if client_id:
                job.clients.add(client_id)
            return self._job_view(job)

    def _autocache_decide(
        self,
        dataset_id: str,
        compression: Optional[str],
        client_codecs: Optional[List[str]],
    ) -> "tuple[str, Optional[str]]":
        """Resolve an autocache job's effective dataset.

        READ swaps the job onto a snapshot-source dataset (registered and
        journaled like any other); WRITE_THROUGH starts materializing the
        pipeline (get-or-start) while the job computes as usual.
        """
        ds = self._datasets[dataset_id]
        d = self._autocache.decide(
            ds.fingerprint, cache_stats=self._aggregate_cache_stats(ds.fingerprint)
        )
        if d.decision == Decision.READ:
            snap_graph = Graph([Node("snapshot", {"path": d.snapshot_path})])
            resp = self.rpc_get_or_register_dataset(snap_graph.to_bytes())
            return resp["dataset_id"], d.value
        if d.decision == Decision.WRITE_THROUGH:
            self.rpc_start_snapshot(
                path=d.snapshot_path,
                dataset_id=dataset_id,
                compression=compression,
                client_codecs=client_codecs,
                # the policy only answers WRITE_THROUGH for an existing dir
                # when the write is abandoned — allow clearing it
                replace_stale_s=self._autocache.config.stale_write_timeout_s,
            )
        return dataset_id, d.value

    def _aggregate_cache_stats(self, cache_key: str) -> Optional[Dict[str, Any]]:
        """Sum heartbeat-reported SlidingWindowCache counters for one key."""
        agg: Dict[str, float] = {}
        found = False
        for w in self._workers.values():
            st = w.cache_stats.get(cache_key)
            if not st:
                continue
            found = True
            for k, v in st.items():
                if isinstance(v, (int, float)):
                    agg[k] = agg.get(k, 0) + v
        return agg if found else None

    # feed-stall reports older than this are ignored by the aggregate — a
    # finished/stuck consumer must not pin the autoscaler's view forever
    STALL_REPORT_TTL_S = 10.0

    def _aggregate_client_stall(self, job: _Job) -> Optional[Dict[str, float]]:
        """Mean of the job's fresh per-client feed-stall windows.

        Expired entries are pruned, not just filtered: client churn on a
        long-lived job (every feeder session is a fresh client_id) must
        not grow the dict without bound.  Callers hold ``self._lock``.
        """
        now = time.monotonic()
        for cid in [
            cid
            for cid, r in job.client_stall.items()
            if now - r.get("t", 0.0) > self.STALL_REPORT_TTL_S
        ]:
            del job.client_stall[cid]
        fresh = list(job.client_stall.values())
        if not fresh:
            return None
        n = len(fresh)

        def mean(key: str) -> float:
            return sum(float(r.get(key, 0.0)) for r in fresh) / n

        return {
            "clients": float(n),
            "stall_frac": mean("stall_frac"),
            "idle_s_per_step": mean("idle_s_per_step"),
            "fetch_s_per_step": mean("fetch_s_per_step"),
            "transfer_s_per_step": mean("transfer_s_per_step"),
        }

    def _apply_job(self, p: Dict[str, Any]) -> _Job:
        job = _Job(
            job_id=p["job_id"],
            job_name=p["job_name"],
            dataset_id=p["dataset_id"],
            policy=ShardingPolicy(p["policy"]),
            num_consumers=p["num_consumers"],
            sharing=p["sharing"],
            compression=p.get("compression"),
            max_workers=p.get("max_workers", 0),
            weight=p.get("weight", 1.0),
            resume_offsets=p.get("resume_offsets", False),
            autocache_decision=p.get("autocache_decision"),
            target_share=p.get("target_share"),
            trace=p.get("trace"),
        )
        if job.policy in (ShardingPolicy.DYNAMIC, ShardingPolicy.STATIC):
            graph = Graph.from_bytes(self._datasets[job.dataset_id].graph_bytes)
            hint = p.get("shard_hint") or max(1, len(self._workers)) * self._overpartition
            job.shard_mgr = ShardManager(
                graph,
                job.policy,
                num_workers_hint=hint,
                overpartition=1,
                resume_offsets=job.resume_offsets,
            )
        self._jobs[job.job_id] = job
        if job.job_name:
            self._jobs_by_name[job.job_name] = job.job_id
        return job

    def _grant_initial_tasks(self, job: _Job) -> None:
        """Initial task grants for a freshly created job.

        Called from the RPC path only, NEVER from replay: task grants mint
        fresh ids and journal ``task_created`` records, and on replay the
        tasks are reconstructed verbatim from those records (the worker
        pool is empty during replay anyway, so granting there is at best a
        no-op and at worst a source of divergence).
        """
        # a new schedulable job starts at its weighted fair share of the
        # fleet, placed on the least-loaded workers (rebalance() adjusts it
        # from demand); unscheduled jobs (and non-scheduling deployments)
        # get a task on every worker (scale-out)
        if self._scheduler is not None and self._schedulable(job):
            if job.target_share is None:
                job.target_share = self._initial_share(job)
            if job.target_share is not None:
                self._apply_share(job, job.target_share)
        else:
            for w in self._workers.values():
                self._ensure_task(job, w.info)

    def _ensure_task(self, job: _Job, w: WorkerInfo) -> Optional[TaskSpec]:
        if job.finished or w.worker_id in job.tasks_by_worker:
            return None
        if (job.job_id, w.worker_id) in self._pending_reclaims:
            # this worker is still draining a retired task for the job:
            # granting a fresh one now would hand the new runner shards
            # while the pending reclaim is about to yank them back
            return None
        # count only ACTIVE tasks (live workers, not completed): tasks left
        # behind by dead workers must not eat into the cap, or a capped job
        # ends up permanently under-provisioned after worker churn
        if job.max_workers or job.target_share is not None:
            active = self._slot_count(job)
            if job.max_workers and active >= job.max_workers:
                return None
            if (
                self._scheduler is not None
                and job.target_share is not None
                and self._schedulable(job)
                and active >= job.target_share
            ):
                return None
        ds = self._datasets[job.dataset_id]
        job.seq += 1
        task = TaskSpec(
            task_id=new_id("task"),
            job_id=job.job_id,
            dataset_id=job.dataset_id,
            worker_id=w.worker_id,
            worker_address=w.address,
            policy=job.policy.value,
            num_consumers=job.num_consumers,
            round_robin=job.num_consumers > 0,
            shared=job.sharing,
            cache_key=ds.fingerprint if job.sharing else None,
            worker_seed=job.seq,
        )
        # journal task creation: task ids must be STABLE across dispatcher
        # restarts so live workers/clients keep their handles (§3.4)
        self._journal.append("task_created", vars(task).copy())
        self._apply_task(job, task)
        return task

    def _apply_task(self, job: _Job, task: TaskSpec) -> None:
        job.tasks[task.task_id] = task
        job.tasks_by_worker[task.worker_id] = task.task_id

    def _job_view(self, job: _Job) -> Dict[str, Any]:
        return {
            "job_id": job.job_id,
            "dataset_id": job.dataset_id,
            "policy": job.policy.value,
            "num_consumers": job.num_consumers,
            "finished": job.finished,
            "worker_list_version": self._worker_list_version,
            "compression": job.compression,
            "autocache": job.autocache_decision,
            "tasks": [vars(t) for t in self._visible_tasks(job)],
        }

    def _visible_tasks(self, job: _Job) -> List[TaskSpec]:
        """Tasks listed to clients.

        Within the post-restore grace window journaled uncompleted tasks
        are listed even though their workers have not re-registered yet:
        only the dispatcher restarted — the workers (and the buffers they
        hold) are still alive at their journaled addresses.  Dropping them
        from the view here would make clients fail their handles, and
        coordinated consumers that heartbeat at different moments during
        the window would remap rounds to different workers (breaking the
        same-bucket-per-round guarantee).  If a worker really did die, the
        grace expires and the next view drops it.
        """
        if (
            self._task_grace_deadline is not None
            and time.monotonic() < self._task_grace_deadline
        ):
            return [
                t for t in job.tasks.values() if t.task_id not in job.completed_tasks
            ]
        return self._active_tasks(job)

    def _active_tasks(self, job: _Job) -> List[TaskSpec]:
        return [
            t
            for t in job.tasks.values()
            if t.task_id not in job.completed_tasks
            and t.worker_id in self._workers
        ]

    def _slot_count(self, job: _Job) -> int:
        """Tasks counted against the job's worker cap/share.

        Normally the ACTIVE tasks; within the post-restore grace window
        every journaled (uncompleted) task holds its slot even though its
        worker has not re-registered yet — the owner is probably mid-
        reconnect, and handing its slot to a faster-registering worker
        would inflate the job past its journaled allocation.
        """
        if (
            self._task_grace_deadline is not None
            and time.monotonic() < self._task_grace_deadline
        ):
            return len(
                [t for t in job.tasks.values() if t.task_id not in job.completed_tasks]
            )
        self._task_grace_deadline = None
        return len(self._active_tasks(job))

    def rpc_client_heartbeat(
        self,
        job_id: str,
        client_id: str,
        starving: bool = False,
        stall_stats: Optional[Dict[str, Any]] = None,
        trace: Optional[Dict[str, Any]] = None,
    ) -> Dict[str, Any]:
        self._crash("client_heartbeat")
        ctx = TraceContext.from_wire(trace) if trace else None
        wall = time.time() if ctx is not None else 0.0
        t0 = time.perf_counter()
        with self._lock:
            job = self._jobs.get(job_id)
            if job is None:
                raise KeyError(f"unknown job {job_id}")
            job.clients.add(client_id)
            if stall_stats:
                job.client_stall[client_id] = {
                    "t": time.monotonic(),
                    **stall_stats,
                }
            self._maybe_finish(job)
            view = self._job_view(job)
            view["starving_ack"] = starving
        if ctx is not None:
            # control-plane span: the chaos suite asserts these keep the
            # job's trace_id across a standby promotion
            self.tracer.record(
                "dispatcher.heartbeat",
                ctx.child(),
                wall,
                time.perf_counter() - t0,
                parent_id=ctx.span_id,
                job_id=job_id,
                client_id=client_id,
            )
        return view

    # ------------------------------------------------------------------
    # Workers
    # ------------------------------------------------------------------
    def rpc_register_worker(
        self, worker_id: str, address: str, tags: Optional[Dict[str, Any]] = None
    ) -> Dict[str, Any]:
        with self._lock:
            self._journal.append(
                "worker_registered", {"worker_id": worker_id, "address": address}
            )
            is_new = worker_id not in self._workers
            # (re)registration resets delivery state — stateless workers that
            # restart must receive their tasks again (paper §3.4)
            self._workers[worker_id] = _Worker(WorkerInfo(worker_id, address, tags or {}))
            if is_new:
                self._worker_list_version += 1
            w = self._workers[worker_id]
            tasks = self._undelivered_tasks(w)
            self._assign_snapshot_streams(worker_id)
            return {
                "tasks": tasks,
                "snapshot_streams": self._undelivered_snapshot_streams(w),
                "worker_list_version": self._worker_list_version,
            }

    def _undelivered_tasks(self, w: _Worker) -> List[Dict[str, Any]]:
        """Tasks for every active job not yet shipped to this worker."""
        out: List[Dict[str, Any]] = []
        for job in self._jobs.values():
            if job.finished:
                continue
            t = self._ensure_task(job, w.info)
            if t is None:
                tid = job.tasks_by_worker.get(w.info.worker_id)
                if tid and tid not in job.completed_tasks:
                    t = job.tasks[tid]
            if t is not None and t.task_id not in w.delivered:
                w.delivered.add(t.task_id)
                out.append(self._task_payload(t, job))
        return out

    def _task_payload(self, t: TaskSpec, job: _Job) -> Dict[str, Any]:
        ds = self._datasets[job.dataset_id]
        p = vars(t).copy()
        p["graph_bytes"] = ds.graph_bytes
        p["compression"] = job.compression
        p["resume_offsets"] = job.resume_offsets
        p["static_shards"] = None
        if job.trace:
            # worker pipeline spans parent to the job's root trace context
            p["trace"] = job.trace
        if job.policy == ShardingPolicy.STATIC and job.shard_mgr is not None:
            # computed ONCE over the workers present at first hand-out (the
            # paper's "up-front" semantics) and journaled for restart stability
            if job.static_assignment is None:
                assignment = job.shard_mgr.static_assignment(
                    sorted(job.tasks_by_worker)
                )
                self._journal.append(
                    "static_assignment",
                    {"job_id": job.job_id, "assignment": assignment},
                )
                job.static_assignment = assignment
            p["static_shards"] = job.static_assignment.get(t.worker_id, [])
        return p

    def rpc_worker_heartbeat(
        self,
        worker_id: str,
        buffer_occupancy: float = 0.0,
        cpu_busy: float = 0.0,
        completed_tasks: Optional[List[str]] = None,
        cache_stats: Optional[Dict[str, Dict[str, Any]]] = None,
        failed_streams: Optional[List[List[Any]]] = None,
    ) -> Dict[str, Any]:
        self._crash("worker_heartbeat")
        with self._lock:
            w = self._workers.get(worker_id)
            if w is None:
                # unknown worker (e.g. dispatcher restarted): ask it to re-register
                return {"reregister": True}
            w.last_heartbeat = time.monotonic()
            w.buffer_occupancy = buffer_occupancy
            w.cpu_busy = cpu_busy
            if cache_stats is not None:
                w.cache_stats = cache_stats
            self._step_pending_reclaims(worker_id)
            for tid in completed_tasks or []:
                self._complete_task(tid, journal=True)
            for sid, stream_id in failed_streams or []:
                # the worker's writer died on an exception: release the
                # stream so it can be retried (here or elsewhere) from the
                # last committed offset
                self._release_failed_stream(sid, int(stream_id), worker_id)
            new_tasks = self._undelivered_tasks(w)
            self._assign_snapshot_streams(worker_id)
            valid = [
                job.tasks_by_worker[worker_id]
                for job in self._jobs.values()
                if worker_id in job.tasks_by_worker and not job.finished
            ]
            return {
                "new_tasks": new_tasks,
                "snapshot_streams": self._undelivered_snapshot_streams(w),
                "valid_tasks": valid,
                "worker_list_version": self._worker_list_version,
                "reregister": False,
            }

    def _complete_task(self, task_id: str, journal: bool) -> None:
        for job in self._jobs.values():
            if task_id in job.tasks and task_id not in job.completed_tasks:
                if journal:
                    self._journal.append("task_completed", {"task_id": task_id})
                job.completed_tasks.add(task_id)
                self._maybe_finish(job)

    def _maybe_finish(self, job: _Job) -> None:
        if job.finished or not job.tasks:
            return
        live = [t for t in job.tasks.values() if t.worker_id in self._workers]
        all_done = all(t.task_id in job.completed_tasks for t in live) and live
        if job.policy == ShardingPolicy.DYNAMIC and job.shard_mgr is not None:
            if job.shard_mgr.done() and all_done:
                self._finish_job(job)
        elif all_done:
            self._finish_job(job)

    def _finish_job(self, job: _Job) -> None:
        self._journal.append("job_finished", {"job_id": job.job_id})
        job.finished = True

    # -- failure detection ------------------------------------------------
    def check_workers(self) -> List[str]:
        """Mark workers dead after heartbeat timeout. Returns removed ids.

        Called by the orchestrator's GC loop (or tests directly).
        """
        if self._failed:
            return []  # crashed dispatcher: the GC loop must not mutate state
        now = time.monotonic()
        removed = []
        with self._lock:
            for wid, w in list(self._workers.items()):
                if now - w.last_heartbeat > self._heartbeat_timeout:
                    removed.append(wid)
                    self._remove_worker(wid)
            self._sweep_orphan_shards(now)
        return removed

    def _sweep_orphan_shards(self, now: float) -> None:
        """Reclaim shards AND snapshot streams assigned (pre-restart, per
        the journal) to workers that never re-registered.  check_workers
        can't see them — they are not in self._workers — so without this
        sweep such shards stay in-flight forever and the job (or snapshot)
        never finishes."""
        if self._orphan_sweep_deadline is None or now < self._orphan_sweep_deadline:
            return
        self._orphan_sweep_deadline = None
        for snap in self._snapshots.values():
            if snap.finished:
                continue
            orphan_owners = {
                s.assigned_to
                for s in snap.streams
                if s.assigned_to and not s.done
                and s.assigned_to not in self._workers
            }
            # sorted: release order feeds journaled stream reassignment, and
            # set order is hash-seed dependent (differs across processes)
            for wid in sorted(orphan_owners):
                self._release_worker_streams(wid)
        for job in self._jobs.values():
            mgr = job.shard_mgr
            if mgr is None or job.finished:
                continue
            orphans = {
                st.assigned_to
                for st in mgr._states
                if st.assigned_to and not st.completed
                and st.assigned_to not in self._workers
            }
            # sorted: shard_lost records land in the journal in this order,
            # and two runs of the same primary must journal identically
            for wid in sorted(orphans):
                for sid in mgr.worker_failed(wid):
                    self._journal.append(
                        "shard_lost",
                        {"job_id": job.job_id, "shard_id": sid, "worker_id": wid},
                    )
            if orphans:
                self._maybe_finish(job)
        # deferred retirement reclaims whose worker never re-registered
        # were just covered by the orphan sweep above
        for key in [k for k in self._pending_reclaims if k[1] not in self._workers]:
            del self._pending_reclaims[key]

    def rpc_remove_worker(self, worker_id: str) -> Dict[str, Any]:
        """Administrative removal (tests / orchestrator-initiated)."""
        with self._lock:
            self._remove_worker(worker_id)
        return {"ok": True}

    def _remove_worker(self, worker_id: str) -> None:
        if worker_id not in self._workers:
            return
        self._journal.append("worker_removed", {"worker_id": worker_id})
        del self._workers[worker_id]
        self._worker_list_version += 1
        # worker death supersedes any deferred retirement reclaim: the
        # worker_failed sweep below covers every job's in-flight shards
        for key in [k for k in self._pending_reclaims if k[1] == worker_id]:
            del self._pending_reclaims[key]
        self._release_worker_streams(worker_id)
        for job in self._jobs.values():
            if job.shard_mgr is not None:
                lost = job.shard_mgr.worker_failed(worker_id)
                for sid in lost:
                    self._journal.append(
                        "shard_lost",
                        {"job_id": job.job_id, "shard_id": sid, "worker_id": worker_id},
                    )
            self._maybe_finish(job)

    # ------------------------------------------------------------------
    # DYNAMIC sharding hand-out (worker-facing)
    # ------------------------------------------------------------------
    def rpc_get_shard(
        self, job_id: str, worker_id: str, holding: Optional[List[int]] = None
    ) -> Dict[str, Any]:
        with self._lock:
            job = self._jobs.get(job_id)
            if job is None or job.shard_mgr is None:
                return {"done": True}
            if worker_id not in job.tasks_by_worker:
                # the worker's task was retired (fleet scheduler) but its
                # runner has not been pruned yet — handing it a shard would
                # strand that shard in-flight forever once the runner stops
                return {"done": True}
            if holding is not None:
                # Reconciliation: shards the journal says this worker holds
                # but the worker does NOT (a "shard_assigned" was journaled
                # and the crash ate the response, or a queued completion ack
                # was lost with the worker) delivered zero bytes worker-side,
                # so re-queuing them is exact — without this they would stay
                # in-flight forever and the job could never finish.
                held = set(holding)
                for sid in job.shard_mgr.assigned_to_worker(worker_id):
                    if sid in held:
                        continue
                    self._journal.append(
                        "shard_requeued",
                        {"job_id": job_id, "shard_id": sid, "worker_id": worker_id},
                    )
                    job.shard_mgr.requeue(sid, worker_id)
            nxt = job.shard_mgr.next_shard(worker_id)
            if nxt is None:
                # resume_offsets: an in-flight shard on a dying worker can
                # RE-ENTER the queue — "empty now" is not "drained".  Tell
                # workers to poll again instead of retiring their task.
                if job.shard_mgr.resume_offsets and not job.shard_mgr.done():
                    return {"done": False, "wait": True}
                return {"done": True}
            sid, shard, offset = nxt
            self._journal.append(
                "shard_assigned",
                {"job_id": job_id, "shard_id": sid, "worker_id": worker_id},
            )
            self._crash("get_shard.journaled")
            return {"done": False, "shard_id": sid, "shard": shard, "offset": offset}

    def rpc_complete_shard(
        self, job_id: str, shard_id: int, worker_id: str
    ) -> Dict[str, Any]:
        with self._lock:
            job = self._jobs.get(job_id)
            if job is not None and job.shard_mgr is not None:
                self._journal.append(
                    "shard_completed",
                    {"job_id": job_id, "shard_id": shard_id, "worker_id": worker_id},
                )
                job.shard_mgr.complete_shard(shard_id, worker_id)
            return {"ok": True}

    def rpc_checkpoint_offset(
        self, job_id: str, shard_id: int, worker_id: str, offset: int
    ) -> Dict[str, Any]:
        with self._lock:
            job = self._jobs.get(job_id)
            if job is not None and job.shard_mgr is not None:
                self._journal.append(
                    "shard_offset",
                    {"job_id": job_id, "shard_id": shard_id, "offset": offset},
                )
                job.shard_mgr.checkpoint_offset(shard_id, worker_id, offset)
            return {"ok": True}

    # ------------------------------------------------------------------
    # Journal replay (control-plane events)
    # ------------------------------------------------------------------
    def apply_control_event(self, etype: str, p: Dict[str, Any]) -> bool:
        """Apply one replayed control-plane event.  Returns False for event
        types this module does not own.  Caller holds ``self._lock``."""
        if etype == "dataset_registered":
            self._apply_dataset(p["dataset_id"], p["graph_bytes"], p["fingerprint"])
        elif etype == "job_created":
            self._apply_job(p)
        elif etype == "job_finished":
            if p["job_id"] in self._jobs:
                self._jobs[p["job_id"]].finished = True
        elif etype == "task_created":
            job = self._jobs.get(p["job_id"])
            if job is not None:
                task = TaskSpec(**p)
                self._apply_task(job, task)
                job.seq = max(job.seq, task.worker_seed)
        elif etype == "task_retired":
            job = self._jobs.get(p["job_id"])
            if job is not None:
                self._apply_task_retired(job, p["task_id"])
        elif etype == "static_assignment":
            job = self._jobs.get(p["job_id"])
            if job is not None:
                job.static_assignment = p["assignment"]
        elif etype == "task_completed":
            self._complete_task(p["task_id"], journal=False)
        elif etype == "shard_assigned":
            job = self._jobs.get(p["job_id"])
            if job and job.shard_mgr:
                # keep the assignment: the worker is (presumably) still
                # alive and processing; heartbeat timeout reclaims it
                mgr = job.shard_mgr
                with mgr._lock:
                    for st in mgr._states:
                        if st.shard_id == p["shard_id"]:
                            st.assigned_to = p["worker_id"]
                    try:
                        mgr._pending.remove(p["shard_id"])
                    except ValueError:
                        pass
        elif etype == "shard_requeued":
            job = self._jobs.get(p["job_id"])
            if job and job.shard_mgr:
                job.shard_mgr.requeue(p["shard_id"], p["worker_id"])
        elif etype == "shard_completed":
            job = self._jobs.get(p["job_id"])
            if job and job.shard_mgr:
                job.shard_mgr.complete_shard(p["shard_id"], p["worker_id"])
        elif etype == "shard_lost":
            job = self._jobs.get(p["job_id"])
            if job and job.shard_mgr:
                for st in job.shard_mgr._states:
                    if st.shard_id == p["shard_id"] and not st.completed:
                        st.lost = True
                        st.assigned_to = None
        elif etype == "shard_offset":
            job = self._jobs.get(p["job_id"])
            if job and job.shard_mgr:
                for st in job.shard_mgr._states:
                    if st.shard_id == p["shard_id"]:
                        st.offset = max(st.offset, p["offset"])
        elif etype in ("worker_registered", "worker_removed"):
            # Deliberate no-ops: workers are transient; they re-register
            # via heartbeat after a dispatcher restart, so replay must NOT
            # resurrect self._workers entries nobody is heartbeating for.
            # Tasks and in-flight shard assignments are preserved verbatim
            # (live workers continue seamlessly); workers that don't come
            # back are invisible to check_workers, and finalize_restore
            # arms the orphan sweep — one heartbeat-timeout of grace, then
            # their in-flight shards are reclaimed.  The events are still
            # journaled because the fleet-membership history is what the
            # orphan sweep and the chaos harness audit.
            pass
        else:
            return False
        return True
