"""Server-side job manager hosted by the head.

Reference: dashboard/modules/job/job_manager.py — there the driver runs
under a supervisor actor; here the head spawns the entrypoint as a child
process with RAY_TPU_ADDRESS injected, which is the same shape without a
dashboard middleman.
"""
from __future__ import annotations

import os
import subprocess
import threading
import time
import uuid
from typing import Any, Dict, List, Optional


class JobStatus:
    PENDING = "PENDING"
    RUNNING = "RUNNING"
    SUCCEEDED = "SUCCEEDED"
    FAILED = "FAILED"
    STOPPED = "STOPPED"

    TERMINAL = (SUCCEEDED, FAILED, STOPPED)


class JobInfo:
    def __init__(self, job_id: str, entrypoint: str,
                 metadata: Optional[Dict[str, str]] = None):
        self.job_id = job_id
        self.entrypoint = entrypoint
        self.metadata = metadata or {}
        self.status = JobStatus.PENDING
        self.message = ""
        self.start_time = time.time()
        self.end_time: Optional[float] = None

    def to_dict(self) -> Dict[str, Any]:
        return {"job_id": self.job_id, "entrypoint": self.entrypoint,
                "status": self.status, "message": self.message,
                "metadata": dict(self.metadata),
                "start_time": self.start_time,
                "end_time": self.end_time}


class JobManager:
    def __init__(self, head_address: str, log_dir: Optional[str] = None):
        self._head_address = head_address
        self._log_dir = log_dir or os.path.join(
            "/tmp", "ray_tpu", f"session_{os.getpid()}", "logs")
        os.makedirs(self._log_dir, exist_ok=True)
        self._lock = threading.Lock()
        self._jobs: Dict[str, JobInfo] = {}
        self._procs: Dict[str, subprocess.Popen] = {}

    def log_path(self, job_id: str) -> str:
        return os.path.join(self._log_dir, f"job-{job_id}.log")

    def submit_job(self, entrypoint: str,
                   submission_id: Optional[str] = None,
                   runtime_env: Optional[Dict[str, Any]] = None,
                   metadata: Optional[Dict[str, str]] = None) -> str:
        job_id = submission_id or f"raytpu-job-{uuid.uuid4().hex[:10]}"
        with self._lock:
            if job_id in self._jobs:
                raise ValueError(f"Job {job_id!r} already exists")
            info = JobInfo(job_id, entrypoint, metadata)
            self._jobs[job_id] = info
        env = dict(os.environ)
        env["RAY_TPU_ADDRESS"] = self._head_address
        env["RAY_TPU_JOB_ID"] = job_id
        cwd = None
        runtime_env = runtime_env or {}
        if runtime_env.get("working_dir"):
            cwd = runtime_env["working_dir"]
        for k, v in (runtime_env.get("env_vars") or {}).items():
            env[k] = str(v)
        log_f = open(self.log_path(job_id), "wb")
        try:
            proc = subprocess.Popen(
                entrypoint, shell=True, cwd=cwd, env=env,
                stdout=log_f, stderr=subprocess.STDOUT,
                start_new_session=True)
        except OSError as e:
            log_f.close()
            with self._lock:
                info.status = JobStatus.FAILED
                info.message = str(e)
                info.end_time = time.time()
            return job_id
        with self._lock:
            info.status = JobStatus.RUNNING
            self._procs[job_id] = proc
        threading.Thread(target=self._wait_job, args=(job_id, proc, log_f),
                         daemon=True, name=f"job-wait-{job_id}").start()
        return job_id

    def _wait_job(self, job_id: str, proc: subprocess.Popen, log_f):
        rc = proc.wait()
        log_f.close()
        with self._lock:
            info = self._jobs[job_id]
            if info.status == JobStatus.STOPPED:
                pass
            elif rc == 0:
                info.status = JobStatus.SUCCEEDED
            else:
                info.status = JobStatus.FAILED
                info.message = f"exit code {rc}"
            info.end_time = time.time()
            self._procs.pop(job_id, None)

    def stop_job(self, job_id: str) -> bool:
        with self._lock:
            info = self._jobs.get(job_id)
            proc = self._procs.get(job_id)
            if info is None:
                raise ValueError(f"No job {job_id!r}")
            if info.status in JobStatus.TERMINAL:
                return False
            info.status = JobStatus.STOPPED
            info.end_time = time.time()
        if proc is not None:
            try:
                proc.terminate()
                try:
                    proc.wait(timeout=3)
                except subprocess.TimeoutExpired:
                    proc.kill()
            except OSError:
                pass
        return True

    def get_job_status(self, job_id: str) -> str:
        with self._lock:
            info = self._jobs.get(job_id)
            if info is None:
                raise ValueError(f"No job {job_id!r}")
            return info.status

    def get_job_info(self, job_id: str) -> Dict[str, Any]:
        with self._lock:
            info = self._jobs.get(job_id)
            if info is None:
                raise ValueError(f"No job {job_id!r}")
            return info.to_dict()

    def get_job_logs(self, job_id: str) -> str:
        path = self.log_path(job_id)
        if not os.path.exists(path):
            return ""
        with open(path, "rb") as f:
            return f.read().decode(errors="replace")

    def list_jobs(self) -> List[Dict[str, Any]]:
        with self._lock:
            return [i.to_dict() for i in self._jobs.values()]

    def shutdown(self):
        with self._lock:
            job_ids = [jid for jid, i in self._jobs.items()
                       if i.status not in JobStatus.TERMINAL]
        for jid in job_ids:
            try:
                self.stop_job(jid)
            except ValueError:
                pass
