#!/usr/bin/env bash
# Provision a TPU VM host to run aiOS-TPU.
#
# TPU-native equivalent of the reference's installer + first-boot pair
# (/root/reference/scripts/install.sh:1, first-boot.sh): where the reference
# builds a bootable ISO with llama.cpp compiled in, a TPU deployment is a
# managed Cloud TPU VM — so "install" means: verify the JAX/TPU stack, lay
# down the directory tree and default config, install a systemd unit for the
# boot supervisor, and (optionally) pull model weights.
#
# Usage:
#   scripts/install-tpu-vm.sh [--prefix /opt/aios] [--with-models] [--systemd]
#
# Idempotent: safe to re-run.
set -euo pipefail

PREFIX=/opt/aios
WITH_MODELS=0
WITH_SYSTEMD=0
REPO_DIR="$(cd "$(dirname "${BASH_SOURCE[0]}")/.." && pwd)"

while [[ $# -gt 0 ]]; do
  case "$1" in
    --prefix) PREFIX="$2"; shift 2 ;;
    --with-models) WITH_MODELS=1; shift ;;
    --systemd) WITH_SYSTEMD=1; shift ;;
    *) echo "unknown flag: $1" >&2; exit 2 ;;
  esac
done

log() { echo "[install] $*"; }

# --- 1. sanity: python + jax + TPU ----------------------------------------
log "checking python environment"
PYTHON=${PYTHON:-python3}
"$PYTHON" - <<'EOF'
import sys
assert sys.version_info >= (3, 11), f"need python >= 3.11, have {sys.version}"
import jax
print(f"[install] jax {jax.__version__}")
devs = jax.devices()
print(f"[install] devices: {devs}")
if devs[0].platform != "tpu":
    sys.exit("[install] no TPU visible: this host cannot serve "
             "(JAX reports %r devices)" % devs[0].platform)
EOF

# --- 2. directory tree -----------------------------------------------------
log "creating directory tree under $PREFIX and /var/lib/aios"
DIRS=(
  "$PREFIX"
  /var/lib/aios/models
  /var/lib/aios/data
  /etc/aios
)
for d in "${DIRS[@]}"; do
  if [[ -w "$(dirname "$d")" || -w "$d" ]] 2>/dev/null; then
    mkdir -p "$d"
  else
    sudo mkdir -p "$d"
    sudo chown "$(id -u):$(id -g)" "$d"
  fi
done

# --- 3. default config (9-section TOML, aios_tpu/boot/config.py schema) ----
CONFIG=/etc/aios/config.toml
if [[ ! -f "$CONFIG" ]]; then
  log "writing default $CONFIG"
  cat > "$CONFIG" <<EOF
[system]
hostname = "$(hostname)"
log_level = "info"
data_dir = "/var/lib/aios/data"

[boot]
health_timeout_seconds = 120
max_restart_attempts = 5
restart_window_seconds = 300

[models]
model_dir = "/var/lib/aios/models"
default_context = 4096
num_slots = 8
warm_compile = true
autoload = true
EOF
else
  log "$CONFIG already exists; leaving it alone"
fi

# --- 4. code ----------------------------------------------------------------
if [[ "$REPO_DIR" != "$PREFIX/repo" ]]; then
  log "syncing repo -> $PREFIX/repo"
  mkdir -p "$PREFIX/repo"
  rsync -a --delete --exclude .git --exclude __pycache__ \
    "$REPO_DIR/" "$PREFIX/repo/"
fi

# --- 5. optional model weights ---------------------------------------------
if [[ "$WITH_MODELS" == 1 ]]; then
  "$REPO_DIR/scripts/download-models.sh" --dest /var/lib/aios/models
fi

# --- 6. optional systemd unit ----------------------------------------------
if [[ "$WITH_SYSTEMD" == 1 ]]; then
  UNIT=/etc/systemd/system/aios.service
  log "installing $UNIT"
  sudo tee "$UNIT" > /dev/null <<EOF
[Unit]
Description=aiOS-TPU boot supervisor
After=network-online.target

[Service]
Type=simple
WorkingDirectory=$PREFIX/repo
Environment=PYTHONPATH=$PREFIX/repo
Environment=AIOS_DATA_DIR=/var/lib/aios/data
Environment=AIOS_MODEL_DIR=/var/lib/aios/models
ExecStart=$PYTHON -m aios_tpu.boot.supervisor
Restart=on-failure
RestartSec=5

[Install]
WantedBy=multi-user.target
EOF
  sudo systemctl daemon-reload
  sudo systemctl enable aios.service
  log "enabled aios.service (start with: sudo systemctl start aios)"
fi

log "done. start manually with: scripts/run-aios.sh"
