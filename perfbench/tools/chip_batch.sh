#!/bin/sh
# Run several cells one after another on the machine with the chip and keep
# every line they print:
#   sh perfbench/tools/chip_batch.sh <label> "<workload> <seed> <seconds> <trace>" ...
# DEADLINE_S, if set, is the number of seconds after the start of this script
# by which the last run has to have ENDED: a run that would not (285 s is
# allowed for one, 320 s for a traced one) is skipped and said so. The batch
# STOPS after the first run that does not exit 0 (a chip call cannot be
# cancelled, and seven runs of a broken deployment cost PR 24 its proving
# sets) unless KEEP_GOING is set. COPY_TO,
# if set, receives a copy of the logs after every run (a batch started in an
# unpacked archive hands its logs to the directory the chip machine returns).
label=$1; shift
out=chiprun_out/perfbench/$label.log
mkdir -p chiprun_out/perfbench
t0=$(date +%s)
for spec in "$@"; do
  set -- $spec
  cost=285; [ "$4" = 1 ] && cost=320
  now=$(( $(date +%s) - t0 ))
  if [ -n "$DEADLINE_S" ] && [ $(( now + cost )) -gt "$DEADLINE_S" ]; then
    echo "=== $spec SKIPPED at ${now}s: would end after the deadline of ${DEADLINE_S}s" | tee -a $out
    continue
  fi
  echo "=== $spec $(date +%T) (${now}s)" | tee -a $out
  python3 perfbench/run.py --workload $1 --seed $2 --seconds $3 --trace $4 >> $out 2>&1
  rc=$?
  echo "rc=$rc $(date +%T)" | tee -a $out
  grep -E '^(setup|reference|ingest|ramp|window|server|counters|trace|profiler|not correct|FAIL|\{"correct)' $out | tail -n 12
  if [ -n "$COPY_TO" ]; then mkdir -p "$COPY_TO" && cp -r chiprun_out/perfbench/. "$COPY_TO"/; fi
  if [ "$rc" != 0 ] && [ -z "$KEEP_GOING" ]; then echo "=== batch stopped: rc=$rc" | tee -a $out; exit $rc; fi
done
