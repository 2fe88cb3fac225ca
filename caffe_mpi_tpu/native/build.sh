#!/bin/sh
# Build the native library in place: one g++ invocation over the four
# sources (about 3 s), no build directory and no cached configuration —
# a checkout copied to another root (the chip tool's sealed machine)
# rebuilds the same way. Output: libcaffe_tpu_native.so next to this
# script, written under a temporary name and renamed so a half-written
# library is never loadable.
#
# The decode plane (decode.cc, ISSUE 10) needs libjpeg + libpng dev
# headers; when either is missing the library still builds with the
# decode entry points stubbed to "unavailable" (-DCAFFE_TPU_NO_CODEC) and
# the Python side stays on its PIL fallback — transform/reader
# functionality never degrades with the codecs.
set -e
cd "$(dirname "$0")"

# codec probe: compile a header-only check rather than guessing paths —
# whatever include dirs the compiler really resolves are what decode.cc
# will see
CODEC_FLAGS="-DCAFFE_TPU_NO_CODEC"
CODEC_LIBS=""
if printf '#include <cstddef>\n#include <cstdio>\n#include <jpeglib.h>\n#include <png.h>\nint main(){return 0;}\n' \
     | g++ -x c++ - -o /dev/null -ljpeg -lpng 2>/dev/null; then
  CODEC_FLAGS=""
  CODEC_LIBS="-ljpeg -lpng"
else
  echo "warning: libjpeg/libpng dev headers not found;" \
       "building transform-only (PIL decode fallback stays active)" >&2
fi

# shellcheck disable=SC2086 — CODEC_* are intentionally word-split flags
g++ -O3 -fPIC -shared -std=c++17 -pthread -Wall $CODEC_FLAGS \
    transform.cc datumdb.cc lmdb_reader.cc decode.cc \
    -o libcaffe_tpu_native.so.tmp $CODEC_LIBS
mv -f libcaffe_tpu_native.so.tmp libcaffe_tpu_native.so
echo "built $(pwd)/libcaffe_tpu_native.so"
