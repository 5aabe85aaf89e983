"""Repo tooling (vctpu-lint, jaxpr_audit, flakehunt) — importable as a
package so ``python -m tools.vctpu_lint`` works from the repo root."""
