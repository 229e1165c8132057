#!/bin/bash
# retry.sh <script> <log> <timeout> : ask for a chip until one is held (a refused/transient call costs nothing)
for i in $(seq 1 60); do
  chiprun --chips 1 --timeout $3 -- bash $1 > $2 2>&1
  if ! grep -q "status=transient" $2; then echo "attempt $i ran" >> $2.attempts; exit 0; fi
  echo "attempt $i: no machine $(date +%H:%M:%S)" >> $2.attempts
  sleep 20
done
