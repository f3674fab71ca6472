#!/usr/bin/env bash
# Run a long CPU job that yields the (single) host core to TPU captures:
# SIGSTOP the whole process group while tools/out/CAPTURING exists
# (raised by whatever capture needs the core), SIGCONT when it clears. The soak pipeline
# is checkpointed and kill-tolerant, so a pause is strictly safe.
#
# Auto-resume (ISSUE 9 satellite, ROADMAP item 5's dangling artifact):
# when the job exits nonzero AND its command line carries a
# --checkpoint-dir, the wrapper re-launches it with --resume appended
# (idempotent: appended once) up to SHEEP_AUTO_RESUME times (default 8,
# 0 disables). That is exactly what the V=2^30 bigv run needed — it
# died at rc=143 ~5h in and sat dead for want of an unattended retry;
# with this wrapper the kill (OOM-killer, session teardown, watchdog
# exit 121) becomes a resume instead of a lost session:
#
#   tools/run_paused_aware.sh s30.log python tools/bigv_scale30.py \
#       --checkpoint-dir tools/out/soak/s30_ckpt
#
# Usage: run_paused_aware.sh LOGFILE CMD ARGS...
set -u
cd "$(dirname "$0")/.."
log=$1; shift
flag=tools/out/CAPTURING
max_resumes=${SHEEP_AUTO_RESUME:-8}

run_once() {
  setsid "$@" >>"$log" 2>&1 &
  pid=$!
  # setsid makes the child its own process-group leader, so pgid == pid —
  # race-free, unlike reading ps before the exec has happened
  pgid=$pid
  stopped=0
  while kill -0 "$pid" 2>/dev/null; do
    if [ -e "$flag" ] && [ "$stopped" = 0 ]; then
      kill -STOP -- "-$pgid" 2>/dev/null && stopped=1
      echo "[pause-wrapper] STOPPED for capture $(date -u +%H:%M:%S)" >>"$log"
    elif [ ! -e "$flag" ] && [ "$stopped" = 1 ]; then
      kill -CONT -- "-$pgid" 2>/dev/null && stopped=0
      echo "[pause-wrapper] RESUMED $(date -u +%H:%M:%S)" >>"$log"
    fi
    sleep 5
  done
  wait "$pid"
}

: >"$log"
run_once "$@"
rc=$?
echo "[pause-wrapper] job exited rc=$rc" >>"$log"

# auto-resume loop: only for checkpointed jobs (without --checkpoint-dir
# a blind rerun would restart from scratch, silently discarding hours),
# and only for nonzero exits
resumable=0
for a in "$@"; do
  [ "$a" = "--checkpoint-dir" ] && resumable=1
done
attempt=0
while [ "$rc" -ne 0 ] && [ "$resumable" = 1 ] && [ "$attempt" -lt "$max_resumes" ]; do
  attempt=$((attempt + 1))
  case " $* " in
    *" --resume "*) ;;  # idempotent: append once
    *) set -- "$@" --resume ;;
  esac
  echo "[pause-wrapper] auto-resume $attempt/$max_resumes: $*" >>"$log"
  run_once "$@"
  rc=$?
  echo "[pause-wrapper] job exited rc=$rc (resume $attempt)" >>"$log"
done
exit "$rc"
