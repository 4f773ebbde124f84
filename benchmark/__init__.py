"""paddle-tpu's benchmark: everything the yardstick is made of lives here."""
