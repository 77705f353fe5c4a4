#!/bin/bash
# Build the reference ExaML (AVX) and its parser as single-process binaries
# using the single-rank MPI shim in tools/mpistub (no MPI in this image).
# Produces /tmp/refexaml/examl-AVX and /tmp/refparser/parse-examl, used by
# the golden-parity tests (tests/test_reference_parity.py).
set -euo pipefail

REF=${REF:-/root/reference}
STUB=$(cd "$(dirname "$0")"/mpistub && pwd)

cp -r "$REF/versionHeader" /tmp/versionHeader 2>/dev/null || true

if [ ! -x /tmp/refparser/parse-examl ]; then
  cp -r "$REF/parser" /tmp/refparser
  make -C /tmp/refparser -f Makefile.SSE3.gcc
fi

if [ ! -x /tmp/refexaml/examl-AVX ]; then
  cp -r "$REF/examl" /tmp/refexaml
  make -C /tmp/refexaml -f Makefile.AVX.gcc CC=gcc CPPFLAGS="-I$STUB"
fi

echo "built: /tmp/refparser/parse-examl /tmp/refexaml/examl-AVX"
