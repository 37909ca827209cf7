# usage: sets.sh <workload> <tag> <seconds> <seed1> ... ; two sets, same seeds
w=$1; tag=$2; secs=$3; shift 3
mkdir -p chiprun_out
for set in A B; do for seed in "$@"; do
  python3 benchmark/run.py --workload $w --seed $seed --seconds $secs --trace 0 > chiprun_out/${tag}_${set}_${seed}.log 2> chiprun_out/${tag}_${set}_${seed}.err
  rc=$?
  echo "{\"set\": \"$set\", \"seed\": $seed, \"rc\": $rc, \"result\": $(tail -n 1 chiprun_out/${tag}_${set}_${seed}.log)}" | tee -a chiprun_out/${tag}_sets.jsonl | cut -c1-700
done; done
