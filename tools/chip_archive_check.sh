#!/bin/sh
# Prove on the chip that the files git would commit are enough.
#
#   tools/chip_archive_check.sh              # python chip_smoke.py, one chip
#   tools/chip_archive_check.sh --chips 4    # the four-chip path
#
# Stages the working tree, unpacks `git archive $(git write-tree)` into
# _archive_check/tree (git-ignored; the chip tool copies it with the rest
# of the directory, .git excluded) and runs chip_smoke.py from the root
# of that copy through the chip tool: nothing git-ignored (the native
# .so, the compile cache, __pycache__) is there unless the run builds it.
set -eu
cd "$(dirname "$0")/.."
git add -A
rm -rf _archive_check
mkdir -p _archive_check/tree
git archive "$(git write-tree)" | tar -x -C _archive_check/tree
chips=1
case " $* " in *" --chips 4 "*) chips=4 ;; esac
exec chiprun --chips "$chips" --timeout 1500 -- \
    sh -c "cd _archive_check/tree && exec python chip_smoke.py $*"
